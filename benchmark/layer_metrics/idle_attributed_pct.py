"""Serving engine: of the seconds the device ran nothing in the traced
window, the share that falls inside a named phase of a ``serving.step``
span or between two consecutive steps.

``idle_by_phase`` is the table behind the scalar. The device's idle
gaps are those of the "XLA Ops" union inside the runner's ``bench.*``
window, exactly as ``trace_reduce.reduce`` takes them; each gap is
split among the step phases that overlap it IN PROPORTION TO THE
OVERLAP, after the phases are laid on the profiler's clock
(``step_spans.profile_clock``). What overlaps no step of the session
is ``unattributed``. A gap inside ``decode_fetch`` or ``prefill_fetch``
is not the host's work: the host is blocked there while the program it
queued has yet to start, pauses between two ops, or has ended and the
wake-up is on its way. ``None`` without a device plane, without the
pairs that tie the two clocks, or when their spread exceeds 1 ms.

The table comes twice, because the trace's device plane and its host
plane do not share a clock exactly and the dump does not say by how
much (``dump_xplane`` drops the planes' offsets: ROADMAP D12(c)).
``idle_s`` takes the trace as it is written: no assumption, and the
scalar reads this one. ``idle_s_shifted`` moves the device plane later
as far as causality allows: a fetch cannot return before its program
ends, so the shift is at most the smallest (end of a ``*_fetch`` phase
- end of the "XLA Modules" event nearest to it). That floor is taken
at the waits' 5th percentile, so that one mis-paired fetch does not
set it, and is ``None`` (no second table) when it is not sharp: the
25th percentile more than 0.5 ms above the 5th. On the v5e it is
sharp (0.06-0.10 ms) and lies 1.1 to 2.4 ms out, by session (PERF.md,
PR 24). Shifted, the quickest wake-up of the session is immediate;
the launch phases' shares are then at their largest and the fetch
phases' at their smallest. Where the two tables differ, neither
settles a launch / fetch split.

    python3 benchmark/layer_metrics/idle_attributed_pct.py [workload]

prints both tables of the last traced run of ``workload`` from
``chiprun_out/benchmark/<workload>/traced.json`` + ``trace_dump.json``.
"""

import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if __name__ == "__main__":  # run as a file: benchmark/ is not importable yet
    sys.path.insert(0, ROOT)

from benchmark import step_spans, trace_reduce as tr  # noqa: E402

BETWEEN = "between_steps"
UNATTRIBUTED = "unattributed"
MAX_SPREAD_NS = 1e6
MAX_FLOOR_SPREAD_NS = 5e5


def _labelled(clock):
    """[lo_ns, hi_ns, name], sorted and disjoint: the phases of every
    step the session covers and the pauses between those steps."""
    def ns(mono, step):
        # mono -> epoch through the step's own (ts, mono) pair.
        t = step["ts"] + (mono - step["mono"])
        return (t - clock["base_ts"]) * 1e9 + clock["origin_ns"]

    out, last = [], None
    for step in clock["steps"]:
        start = ns(step["mono"], step)
        if last is not None and (
            step["attrs"]["idx"] == last[0] + 1 and start > last[1]
        ):
            out.append([last[1], start, BETWEEN])
        for name, offset, dur in step["attrs"]["phases"]:
            lo = ns(step["mono"] + offset, step)
            out.append([lo, lo + dur * 1e9, name])
        last = (step["attrs"]["idx"],
                ns(step["mono"] + step["dur_s"], step))
    return out


def _device_shift_ns(labelled, planes):
    """How far later the device plane may lie at most, or None."""
    ends = sorted(
        r[1] + r[2] for lines in planes.values()
        for r in lines.get(tr.MODULES_LINE) or ()
    )
    waits = []
    for _lo, hi, name in labelled if ends else ():
        if name.endswith("_fetch"):
            i = bisect.bisect_left(ends, hi)
            waits.append(min(
                (hi - e for e in ends[max(i - 1, 0):i + 1]), key=abs
            ))
    if not waits:
        return None
    waits.sort()
    floor = waits[int(0.05 * len(waits))]
    if waits[int(0.25 * len(waits))] - floor > MAX_FLOOR_SPREAD_NS:
        return None
    return floor


def _idle_ns(planes, labelled, lo, hi, shift):
    """({phase: idle ns summed over the device planes}, their number)
    with every device event moved ``shift`` ns later."""
    idle_ns, n_planes = {}, 0
    for lines in planes.values():
        rows = lines.get(tr.OPS_LINE)
        if not rows:
            continue
        busy = tr._clip(
            tr.union([r[1] + shift, r[1] + r[2] + shift] for r in rows),
            lo, hi,
        )
        if not busy:
            continue
        n_planes += 1
        cursor = 0
        for g_lo, g_hi in tr._gaps(busy, lo, hi):
            while cursor < len(labelled) and labelled[cursor][1] <= g_lo:
                cursor += 1
            left, i = g_hi - g_lo, cursor
            while i < len(labelled) and labelled[i][0] < g_hi:
                p_lo, p_hi, name = labelled[i]
                overlap = min(g_hi, p_hi) - max(g_lo, p_lo)
                if overlap > 0:
                    idle_ns[name] = idle_ns.get(name, 0.0) + overlap
                    left -= overlap
                i += 1
            if left > 0:
                idle_ns[UNATTRIBUTED] = idle_ns.get(UNATTRIBUTED, 0.0) + left
    return idle_ns, n_planes


def idle_by_phase(facts):
    """{"idle_s": {phase: seconds}, "idle_s_shifted": the same with the
    device plane at its latest or None, "window_s", "clock"} or None."""
    dump = facts.get("dump") or {}
    clock = step_spans.profile_clock(facts)
    host = dump.get("host")
    if clock is None or clock["spread_ns"] > MAX_SPREAD_NS or not host:
        return None
    lo = min(r[1] for r in host)
    hi = max(r[1] + r[2] for r in host)
    labelled = _labelled(clock)
    planes = dump.get("planes", {})
    idle_ns, n_planes = _idle_ns(planes, labelled, lo, hi, 0.0)
    if not n_planes:
        return None
    shift = _device_shift_ns(labelled, planes)
    shifted = None
    if shift is not None:
        shifted = _idle_ns(planes, labelled, lo, hi, shift)[0]

    def seconds(table):
        return {k: v / n_planes / 1e9 for k, v in table.items()}

    return {
        "idle_s": seconds(idle_ns),
        "idle_s_shifted": None if shifted is None else seconds(shifted),
        "window_s": (hi - lo) / 1e9,
        "clock": dict(
            {k: clock[k] for k in ("origin_ns", "spread_ns", "pairs")},
            shift_ns=shift,
        ),
    }


def read(facts):
    table = idle_by_phase(facts)
    total = sum(table["idle_s"].values()) if table else 0.0
    if not total:
        return None
    return 100.0 * (1.0 - table["idle_s"].get(UNATTRIBUTED, 0.0) / total)


def main(argv):
    workload = argv[1] if len(argv) > 1 else "nemo12b-serve-chat"
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", workload)
    with open(os.path.join(out_dir, "traced.json")) as f:
        facts = json.load(f)
    with open(os.path.join(out_dir, "trace_dump.json")) as f:
        facts["dump"] = json.load(f)
    table = idle_by_phase(facts)
    if table is None:
        print("no device plane, or the clocks could not be tied")
        return 1
    total = sum(table["idle_s"].values())
    shifted = table["idle_s_shifted"]
    shift = table["clock"]["shift_ns"]
    print(f"device idle {total:.6f} s of {table['window_s']:.6f} s traced "
          f"({100 * total / table['window_s']:.2f} %); clock pairs "
          f"{table['clock']['pairs']}, spread "
          f"{table['clock']['spread_ns'] / 1e3:.1f} us")
    if shifted is None:
        print("no second table: the fetches' waits have no sharp floor")
        shifted = {}
    else:
        print(f"second column: device plane {shift / 1e3:.1f} us later, "
              f"its latest; idle then {sum(shifted.values()):.6f} s")
    total_shifted = sum(shifted.values())
    print(f"{'phase':<16}{'as traced':>14}{'%':>8}{'at latest':>14}{'%':>8}")
    for name, s in sorted(table["idle_s"].items(), key=lambda kv: -kv[1]):
        line = f"{name:<16}{s:>12.6f} s{100 * s / total:>8.2f}"
        if name in shifted:
            line += (f"{shifted[name]:>12.6f} s"
                     f"{100 * shifted[name] / total_shifted:>8.2f}")
        print(line)
    print(f"idle_attributed_pct {read(facts):.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
