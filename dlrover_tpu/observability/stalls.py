"""Why a step took far longer than its like: one classifier over spans.

A pure function over FINISHED span dicts (a Tracer's ring, a JSONL
sink), off every hot path; the benchmark's stall readers
(``benchmark/stall_spans.py``) and ``tools/trace_query.py --stalls``
both call it (docs/DESIGN.md §29).

A step's PERIOD is its start to the next step's start: what one
iteration cost its caller, whatever the time went into. Steps are
grouped by KIND from their own counts (``chunk``: ``prefill_tokens``
> 0, ``decode``: ``n_decoding`` > 0, ``both``; a step that launched
neither is not judged: an engine without work waits by design) and a
kind has its median period ``m``. A step is STALLED when ``period - m
>= max(MIN_EXCESS_S, m)``, and ``period - m`` is its EXCESS.

Its CAUSE is the first of these that applies:

1. ``machine``: a ``host.pause`` span the machine made overlaps
   ``[start, next start)`` (``pause_cause``: no collection covers it
   and the process burned under half its length in CPU,
   ``process_cpu_s``): every thread of the process stood still.
2. ``interpreter``: a ``host.pause`` over which the process burned
   about its length in CPU (``INTERPRETER_CPU_SHARE`` of it or more):
   a thread held the interpreter and kept the watcher from running.
   Where ``host.gc`` spans cover half of a pause or more the collection
   is what held it, and the cause is ``gc``, whatever the CPU clock
   says (on the v5e's host every such pause inside a timed window was
   a generation-2 collection of 55-212 ms: PERF.md, PR 53). A pause
   between the two shares is ``unattributed``: the clock cannot tell
   (the v5e's host charges a standstill 0.00-0.08 s of ~0.11 to
   whatever threads were running, and ticks at 10 ms). The
   benchmark's readers count such a pause with the machine's and keep
   it out of what is the program's to mend: every one met so far was
   the machine's. Of several pauses over one step the first of
   ``PAUSE_CAUSES`` names it.
3. ``compile``: a ``compile.backend`` / ``compile.trace_lower`` span
   overlaps, or the step carries ``retraces``.
4. ``gc``: a ``host.gc`` span (a generation-2 collection) overlaps.
5. Otherwise the PHASE that exceeds its own kind's median by most:
   ``device_wait`` where that is ``decode_fetch`` / ``prefill_fetch``
   (the host was alive and blocked on the device), ``caller`` where
   it is the pause between this span's end and the next one's start
   (the replica loop did not call ``step()``), else ``host:<phase>``.
   A step without phases (``train.step``) is ``unknown``.

The order matters: each pause of the machine PR 48 caught lay INSIDE
one ``step()`` whose ``device_get`` waited that long, so without the
watcher's span it reads as ``device_wait``.

Clocks: spans of one process are laid over each other on ``mono``;
spans are judged a ``pid`` at a time, so sinks of several processes
may be read together. The window ``lo`` / ``hi`` is in epoch seconds
(``ts``) and holds the steps that END inside it; the medians are the
window's own.
"""

import math
import statistics
from typing import Dict, Iterable, List, Optional

from dlrover_tpu.observability.host_watch import GC, PAUSE

SERVING_STEP = "serving.step"
COMPILES = ("compile.backend", "compile.trace_lower")
FETCHES = ("decode_fetch", "prefill_fetch")
CALLER = "caller"
MIN_EXCESS_S = 0.05
MACHINE_CPU_SHARE = 0.5
# A 10 ms tick under a 0.07 s hold reads 0.86 of it; the v5e's
# standstills read up to 0.75 of theirs.
INTERPRETER_CPU_SHARE = 0.8
PAUSE_CAUSES = ("machine", "gc", "interpreter", "unattributed")


def pause_cause(pause: Dict, collections: Iterable[Dict] = ()) -> str:
    """Whose a ``host.pause`` is: ``gc`` where ``host.gc`` spans cover
    half of it or more, else by the CPU the process burned over it
    (``process_cpu_s``): ``machine`` under ``MACHINE_CPU_SHARE`` of its
    length, ``interpreter`` from ``INTERPRETER_CPU_SHARE`` up,
    ``unattributed`` between."""
    lo, hi = pause["mono"], pause["mono"] + pause["dur_s"]
    covered = sum(
        min(hi, c["mono"] + c["dur_s"]) - max(lo, c["mono"])
        for c in _overlapping(collections, lo, hi)
    )
    if covered >= 0.5 * pause["dur_s"] > 0:
        return "gc"
    burned = pause["attrs"].get("process_cpu_s", 0.0)
    if burned < MACHINE_CPU_SHARE * pause["dur_s"]:
        return "machine"
    if burned >= INTERPRETER_CPU_SHARE * pause["dur_s"]:
        return "interpreter"
    return "unattributed"


def _kind(step: Dict) -> Optional[str]:
    attrs = step["attrs"]
    if "phases" not in attrs:
        return "step"  # train.step: one kind
    chunk = attrs.get("prefill_tokens", 0) > 0
    decode = attrs.get("n_decoding", 0) > 0
    if chunk and decode:
        return "both"
    return "chunk" if chunk else "decode" if decode else None


def _idx(step: Dict):
    attrs = step["attrs"]
    return attrs.get("idx", attrs.get("step"))


def _parts(step: Dict, period_s: float) -> Dict[str, float]:
    """Seconds by phase, and ``caller``: what of the period lay
    outside the span."""
    out = {CALLER: period_s - step["dur_s"]}
    for name, _offset, dur in step["attrs"].get("phases") or ():
        out[name] = out.get(name, 0.0) + dur
    return out


def _overlapping(spans: List[Dict], lo: float, hi: float) -> List[Dict]:
    return [s for s in spans if s["mono"] < hi and s["mono"] + s["dur_s"] > lo]


def _judged(spans: Iterable[Dict], step_name: str, lo, hi):
    """Per pid: ``(steps with a period and a kind that end in the
    window, that pid's other spans by name)``. A step's period needs
    the next step of its engine: the one after it by start whose
    ``idx`` follows its own."""
    by_pid: Dict = {}
    for s in spans:
        if s.get("dur_s") is not None:
            by_pid.setdefault(s.get("pid"), []).append(s)
    for group in by_pid.values():
        steps = sorted(
            (s for s in group if s["name"] == step_name),
            key=lambda s: s["mono"],
        )
        judged = []
        for step, nxt in zip(steps, steps[1:]):
            a, b = _idx(step), _idx(nxt)
            if a is not None and b is not None and b != a + 1:
                continue  # a gap in the ring, or another engine's step
            end_ts = step["ts"] + step["dur_s"]
            kind = _kind(step)
            if kind is None or not lo <= end_ts <= hi:
                continue
            judged.append((step, nxt["mono"] - step["mono"], kind))
        others: Dict[str, List[Dict]] = {}
        for s in group:
            if s["name"] in (PAUSE, GC) + COMPILES:
                others.setdefault(s["name"], []).append(s)
        yield judged, others


def _cause(step, period_s, parts_median, others) -> str:
    lo, hi = step["mono"], step["mono"] + period_s
    whose = {
        pause_cause(p, others.get(GC, ()))
        for p in _overlapping(others.get(PAUSE, ()), lo, hi)
    }
    for cause in PAUSE_CAUSES:
        if cause in whose:
            return cause
    if step["attrs"].get("retraces") or any(
        _overlapping(others.get(name, ()), lo, hi) for name in COMPILES
    ):
        return "compile"
    if _overlapping(others.get(GC, ()), lo, hi):
        return "gc"
    if "phases" not in step["attrs"]:
        return "unknown"
    parts = _parts(step, period_s)
    worst = max(parts, key=lambda p: parts[p] - parts_median.get(p, 0.0))
    if worst in FETCHES:
        return "device_wait"
    return worst if worst == CALLER else "host:" + worst


def stalls(spans: Iterable[Dict], step_name: str = SERVING_STEP,
           lo: Optional[float] = None,
           hi: Optional[float] = None) -> List[Dict]:
    """The stalled steps, by time: ``{idx, ts, period_s, excess_s,
    kind, cause}`` each."""
    return summary(spans, step_name, lo, hi)["stalls"]


def summary(spans: Iterable[Dict], step_name: str = SERVING_STEP,
            lo: Optional[float] = None,
            hi: Optional[float] = None) -> Dict:
    """``{"stalls": the records, "steps": how many were judged,
    "window_s": ``hi - lo``, or the judged steps' summed periods where
    the window is open, "excess_s": {cause: seconds}, "count": {cause:
    stalled steps}}``."""
    lo = -math.inf if lo is None else lo
    hi = math.inf if hi is None else hi
    found, n_steps, period_sum = [], 0, 0.0
    for judged, others in _judged(spans, step_name, lo, hi):
        n_steps += len(judged)
        period_sum += sum(period for _, period, _ in judged)
        for kind in {k for _, _, k in judged}:
            same = [(s, p) for s, p, k in judged if k == kind]
            median = statistics.median(p for _, p in same)
            all_parts = [_parts(s, p) for s, p in same]
            parts_median = {
                name: statistics.median(
                    parts.get(name, 0.0) for parts in all_parts
                )
                for name in {n for parts in all_parts for n in parts}
            }
            for step, period in same:
                excess = period - median
                if excess < max(MIN_EXCESS_S, median):
                    continue
                found.append({
                    "idx": _idx(step), "ts": step["ts"],
                    "period_s": period, "excess_s": excess, "kind": kind,
                    "cause": _cause(step, period, parts_median, others),
                })
    found.sort(key=lambda r: r["ts"])
    excess_s: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for r in found:
        excess_s[r["cause"]] = excess_s.get(r["cause"], 0.0) + r["excess_s"]
        count[r["cause"]] = count.get(r["cause"], 0) + 1
    return {
        "stalls": found, "steps": n_steps,
        "window_s": hi - lo if math.isfinite(hi - lo) else period_sum,
        "excess_s": excess_s, "count": count,
    }


def render(table: Dict, t0: float = 0.0) -> List[str]:
    """``summary``'s table as lines: the stalled steps (seconds from
    ``t0``), then the seconds lost by cause with their share of the
    window."""
    lines = [f"{'t_s':>17}{'idx':>9}  {'kind':<7}{'period_ms':>11}"
             f"{'excess_ms':>11}  cause"]
    for r in table["stalls"]:
        lines.append(
            f"{r['ts'] - t0:>17.3f}{r['idx']!s:>9}  {r['kind']:<7}"
            f"{r['period_s'] * 1e3:>11.3f}{r['excess_s'] * 1e3:>11.3f}"
            f"  {r['cause']}"
        )
    lines.append(f"{'cause':<24}{'steps':>7}{'excess_s':>11}{'window%':>10}")
    for cause, seconds in sorted(
        table["excess_s"].items(), key=lambda kv: -kv[1]
    ):
        share = 100.0 * seconds / table["window_s"]
        lines.append(f"{cause:<24}{table['count'][cause]:>7}"
                     f"{seconds:>11.3f}{share:>10.3f}")
    return lines
