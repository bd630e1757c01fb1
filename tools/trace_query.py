"""Query/summarize distributed-trace JSONL (observability §29).

Operates on the span sinks written by ``dlrover_tpu.observability.
tracing`` (``DLROVER_TPU_TRACE_FILE``, the fleet soak's
``spans_*.jsonl``, a replica's per-process sink):

    # the 10 slowest spans across files
    python tools/trace_query.py spans_router.jsonl spans_replica0.jsonl

    # per-span-name latency table (count / mean / p50 / p95 / max)
    python tools/trace_query.py --summary spans_*.jsonl

    # master control-plane verbs only: master.<RequestType> server
    # spans folded into the same table, one row per verb — the span
    # mirror of /metrics' master_rpc_seconds{verb} (§32), for
    # cross-checking metrics against traces
    python tools/trace_query.py --verbs spans_master.jsonl

    # serving request lifecycle only: serving.* spans folded into a
    # per-phase table (queue_wait / prefill / migrate / decode — the
    # migrate row is the §36 KV hand-off window between tiers — and
    # with speculative decoding the decode.draft / decode.verify
    # split, §35) plus each phase's share of serving.request time
    python tools/trace_query.py --serving spans_engine.jsonl

    # engine iterations: the phases of the serving.step spans folded
    # into one table with each phase's share of the summed step time,
    # then the steps' counts (admitted / finished requests, prompt
    # tokens, mean decode batch and cache rows visible to it, the share
    # of decode launches enqueued with a step still in flight, which
    # steps recompiled)
    python tools/trace_query.py --steps spans_engine.jsonl

    # why an iteration took far longer than its like: every
    # serving.step whose period (start to the next step's start)
    # exceeds its kind's median by max(0.05 s, median), put down to
    # the machine (a host.pause span with little CPU burned), the
    # interpreter (about its length burned; "unattributed" between
    # the two), a compile, a collection (host.gc), the device, the
    # caller or a host phase, and the seconds lost by cause
    # (observability/stalls.py; --step-name train.step for a trainer)
    python tools/trace_query.py --stalls spans_engine.jsonl

    # a worker's or a replica's start: seconds compiling, loading
    # from the persistent cache and tracing + lowering by program (with
    # how the cache answered each compile), the cache directory as the
    # process found it, and the engine's build and warm-up phases
    python tools/trace_query.py --setup spans_engine.jsonl

    # one trace's tree + critical path (a serving.step: its phases)
    python tools/trace_query.py --trace 7f3a... spans_*.jsonl

    # a traced benchmark run's DEVICE ops under one named scope of one
    # program, by op (the file is the run's trace_dump.json, not a span
    # sink): ms and ops a launch of every (op kind, what it ran under
    # below the scope), largest first
    python tools/trace_query.py --device-ops select --program jit_step \
        chiprun_out/benchmark/sala-serve-docs-64k/trace_dump.json

Plain stdlib + the tracing module's own loaders — usable on any box
that has the repo, no collector service required.
"""

import argparse
import bisect
import collections
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.observability import host_watch, stalls  # noqa: E402
from dlrover_tpu.observability.tracing import (  # noqa: E402
    build_trees,
    load_spans,
)


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(int(q / 100.0 * len(ordered)), len(ordered) - 1)
    return ordered[idx]


def slowest(spans: List[Dict], top: int = 10,
            name: Optional[str] = None) -> List[Dict]:
    pool = [
        s for s in spans
        if s.get("dur_s") is not None
        and (name is None or s.get("name") == name)
    ]
    pool.sort(key=lambda s: -s["dur_s"])
    return pool[:top]


def verb_summary(spans: List[Dict]) -> List[Dict]:
    """The §32 per-verb table from ``master.<RequestType>`` server
    spans: same columns as :func:`summarize`, the ``master.`` prefix
    stripped so rows line up with ``master_rpc_seconds{verb}``
    label values when cross-checking metrics against spans."""
    rows = summarize([
        {**s, "name": s.get("name", "")[len("master."):]}
        for s in spans
        if s.get("name", "").startswith("master.")
        and s.get("kind") == "server"
    ])
    return rows


def serving_summary(spans: List[Dict]) -> List[Dict]:
    """Per-phase table from the engine's ``serving.*`` request spans
    (§25/§35): one row per lifecycle phase (``queue_wait``,
    ``prefill``, ``decode``; ``migrate`` when the fleet migrated KV
    between tiers, §36; and — when speculation ran —
    ``decode.draft``/``decode.verify``), the ``serving.`` prefix
    stripped, plus ``share_pct``: that phase's summed duration over
    the summed ``serving.request`` duration. The draft/verify split is
    how a speculative deployment answers "where does the step time
    go" without a profiler attached; the migrate row is the same
    question for the disaggregated hand-off — its share IS the
    migration tax on request time (phases tile the request, so
    queue + prefill + migrate + decode ≈ e2e — the fleet soak asserts
    exactly this)."""
    rows = summarize([
        {**s, "name": s.get("name", "")[len("serving."):]}
        for s in spans
        if s.get("name", "").startswith("serving.")
        # serving.step times an engine iteration, not a request phase.
        and s.get("name") not in ("serving.request", "serving.step")
    ])
    total = sum(
        s.get("dur_s") or 0.0
        for s in spans
        if s.get("name") == "serving.request"
    )
    for r in rows:
        summed = r["mean_s"] * r["count"]
        r["share_pct"] = round(100.0 * summed / total, 2) if total else 0.0
    return rows


def step_summary(spans: List[Dict]) -> Dict:
    """Where an engine iteration's time goes, from the engine's own
    ``serving.step`` spans (§29). ``phases``: one row per step phase,
    :func:`summarize`'s columns over the steps that passed through it,
    plus ``share_pct`` of the summed step time (phases tile a step, so
    the shares sum to 100). ``counts``: what the steps carried —
    ``n_admitted`` explains a long ``admit`` (a prefix lookup per
    admission), ``n_finished`` a long ``commit`` (a finished request
    emits its span tree there), ``kv_rows_mean`` is the cache rows
    visible to a decode launch (over ``decode_batch_mean`` x the
    engine's ``max_len``: the share of the logical view attention has
    any use for), ``prefill_kv_rows_mean`` the same for a prefill
    chunk launch (the slot's fill below the chunk plus the chunk, over
    ``max_len``), ``overlapped_pct`` the share of decode launches
    enqueued while the previous launch's tokens were still on the
    device (one step in flight: ~100 in steady state, 0 on a path that
    drains every step), ``retraced_steps`` names the iterations that
    recompiled a program, ``prefill_chunks_mean`` is the chunk
    launches of a step that carried any (1 or 2:
    ``Scheduler.pick_prefills``) and ``two_chunk_steps_pct`` the share
    of those steps that carried two (both absent from a sink older than
    the count; ``prefill_tokens`` sums the steps' ``prefill_rows``
    where they have it, every launch's rows, else the one launch's
    ``prefill_tokens``). A sparse-attention expert model's steps also
    count ``selected_rows_mean`` (the rows a decode launch's attention
    reads, beside the ``kv_rows_mean`` it could), ``experts_hit_mean``
    (distinct experts a launch's tokens reach, mean over layers) and
    ``prefix_hit_tokens`` (prompt rows the prefix cache supplied); a
    dense model's table has none of the three. A model whose pool is in
    layer groups adds ``window_rows_mean`` (the rows a decode launch
    reads of a group that keeps only what a query can see) and
    ``window_blocks_released`` (blocks of it the steps' slots gave
    back). A model of lightning and block-sparse layers (PR 55) adds
    ``ckey_rows_mean`` (compressed keys a decode launch scores) and
    ``state_slots_mean`` (slots whose state it updates), and any model
    with per-slot state the sums ``state_snapshots``,
    ``state_restores_from_snapshot`` and ``prefix_rounded_down_blocks``."""
    steps = [
        s for s in spans
        if s.get("name") == "serving.step" and s.get("dur_s") is not None
    ]
    rows = summarize([
        {"name": name, "dur_s": dur, "status": s.get("status")}
        for s in steps for name, _offset, dur in s["attrs"]["phases"]
    ])
    total = sum(s["dur_s"] for s in steps)
    for r in rows:
        summed = r["mean_s"] * r["count"]
        r["share_pct"] = round(100.0 * summed / total, 2) if total else 0.0
    attrs = [s["attrs"] for s in steps]
    decoding = [a["n_decoding"] for a in attrs if a["n_decoding"]]
    kv_rows = [a["kv_rows"] for a in attrs if "kv_rows" in a]
    chunk_rows = [
        a["prefill_kv_rows"] for a in attrs if "prefill_kv_rows" in a
    ]
    return {"phases": rows, "counts": {
        "steps": len(steps),
        "errors": sum(s.get("status") != "ok" for s in steps),
        "admitted": sum(a["n_admitted"] for a in attrs),
        "finished": sum(a["n_finished"] for a in attrs),
        "prefill_tokens": sum(
            a.get("prefill_rows", a["prefill_tokens"]) for a in attrs
        ),
        "decode_batch_mean": (
            sum(decoding) / len(decoding) if decoding else 0.0
        ),
        "kv_rows_mean": sum(kv_rows) / len(kv_rows) if kv_rows else 0.0,
        "prefill_kv_rows_mean": (
            sum(chunk_rows) / len(chunk_rows) if chunk_rows else 0.0
        ),
        "overlapped_pct": (
            100.0 * sum(a.get("overlapped", 0) for a in attrs)
            / len(decoding) if decoding else 0.0
        ),
        "retraced_steps": [a["idx"] for a in attrs if a.get("retraces")],
        **_optional_counts(attrs),
    }}


def setup_summary(spans: List[Dict]) -> Dict:
    """A process's start from its own spans (§29): the program's one
    aggregation (``common/compile_cache.py``'s ``setup_summary``) over
    the sink's ``compile.*`` and engine set-up spans. The sink of a
    process armed after its first compile holds only what came
    later."""
    # Imported here: the module listens to JAX, so it imports it.
    from dlrover_tpu.common import compile_cache

    return compile_cache.setup_summary(
        compile_cache.records_from_spans(spans), spans
    )


def _print_setup(table: Dict) -> None:
    cache = table["cache"]
    if cache:
        print(f"cache directory at start: {cache['entries']} entries, "
              f"{cache['bytes']} bytes")
    print(f"{'program':<36}{'compile_s':>11}{'load_s':>9}{'saved_s':>9}"
          f"{'trace_lower_s':>15}{'hit':>5}{'written':>9}{'uncached':>10}")
    for r in table["programs"] + [dict(table["totals"], name="(all)")]:
        print(
            f"{r['name'][:35]:<36}{r['compile_s']:>11.3f}"
            f"{r['cache_load_s']:>9.3f}{r['saved_s']:>9.3f}"
            f"{r['trace_lower_s']:>15.3f}"
            f"{r['hit']:>5}{r['written']:>9}{r['uncached']:>10}"
        )
    for e in table["engine"]:
        sizes = "  ".join(
            f"{k}={v}" for k, v in e.items()
            if k not in ("name", "dur_s", "phases")
        )
        print(f"{e['name']} {e['dur_s']:.3f} s  {sizes}")
        for phase, seconds in e["phases"]:
            print(f"  {seconds:>9.3f} s  {phase}")


def _print_stalls(table: Dict, spans: List[Dict]) -> None:
    watch = (
        "host watcher on"
        if any(s.get("name") == host_watch.WATCH for s in spans)
        else "NO host.watch span: pauses and collections went "
        "unrecorded and read as device_wait or a host phase"
    )
    print(f"{table['steps']} steps judged over {table['window_s']:.3f} s, "
          f"{len(table['stalls'])} stalled; {watch}")
    print("\n".join(stalls.render(table)))


def _optional_counts(attrs: List[Dict]) -> Dict:
    out = {}
    chunks = [a["prefill_chunks"] for a in attrs if a.get("prefill_chunks")]
    if chunks:
        out["prefill_chunks_mean"] = sum(chunks) / len(chunks)
        out["two_chunk_steps_pct"] = (
            100.0 * sum(c > 1 for c in chunks) / len(chunks)
        )
    for name, count in (("selected_rows_mean", "selected_rows"),
                        ("experts_hit_mean", "experts_hit"),
                        ("window_rows_mean", "window_rows"),
                        ("ckey_rows_mean", "ckey_rows"),
                        ("state_slots_mean", "state_slots")):
        values = [a[count] for a in attrs if count in a]
        if values:
            out[name] = sum(values) / len(values)
    if any("window_blocks_released" in a for a in attrs):
        # a pool in layer groups (kvpool/groups.py): blocks the steps'
        # slots released as their rows slid out of a window's reach
        out["window_blocks_released"] = sum(
            a.get("window_blocks_released", 0) for a in attrs
        )
    for name in ("state_snapshots", "state_restores_from_snapshot",
                 "prefix_rounded_down_blocks"):
        # a model with per-slot state (kvpool/layout.py): snapshots the
        # steps' chunks wrote, admissions a snapshot restored, hit blocks
        # given up for want of one
        if any(name in a for a in attrs):
            out[name] = sum(a.get(name, 0) for a in attrs)
    if any("prefix_hit_tokens" in a for a in attrs):
        out["prefix_hit_tokens"] = sum(
            a.get("prefix_hit_tokens", 0) for a in attrs
        )
    return out


def summarize(spans: List[Dict]) -> List[Dict]:
    """Per-name latency table, slowest-by-p95 first."""
    by_name: Dict[str, List[float]] = {}
    errors: Dict[str, int] = {}
    for record in spans:
        dur = record.get("dur_s")
        if dur is None:
            continue
        name = record.get("name", "?")
        by_name.setdefault(name, []).append(dur)
        if record.get("status") not in ("ok", None):
            errors[name] = errors.get(name, 0) + 1
    rows = []
    for name, durs in by_name.items():
        rows.append({
            "name": name,
            "count": len(durs),
            "errors": errors.get(name, 0),
            "mean_s": sum(durs) / len(durs),
            "p50_s": _percentile(durs, 50),
            "p95_s": _percentile(durs, 95),
            "max_s": max(durs),
        })
    rows.sort(key=lambda r: -r["p95_s"])
    return rows


def critical_path(spans: List[Dict], trace_id: str) -> List[Dict]:
    """Longest-duration root-to-leaf chain of one trace: at each level,
    descend into the slowest child. Each hop reports its duration and
    its SELF time (duration minus its children's sum) — the hop where
    self time dominates is where the wall-clock went."""
    trace_spans = [s for s in spans if s.get("trace_id") == trace_id]
    roots = build_trees(trace_spans)
    if not roots:
        return []
    node = max(roots, key=lambda r: r.get("dur_s") or 0.0)
    path = []
    while node is not None:
        children = node.get("children", [])
        child_sum = sum(c.get("dur_s") or 0.0 for c in children)
        dur = node.get("dur_s") or 0.0
        path.append({
            "name": node.get("name"),
            "span_id": node.get("span_id"),
            "service": node.get("service", ""),
            "status": node.get("status"),
            "dur_s": dur,
            "self_s": max(dur - child_sum, 0.0),
            "attrs": node.get("attrs", {}),
        })
        node = (
            max(children, key=lambda c: c.get("dur_s") or 0.0)
            if children else None
        )
    return path


def render_tree(node: Dict, indent: int = 0) -> List[str]:
    dur = node.get("dur_s")
    dur_txt = f"{dur * 1e3:9.3f}ms" if dur is not None else "      ...  "
    status = node.get("status", "ok")
    mark = "" if status == "ok" else f"  [{status}]"
    lines = [
        f"{dur_txt}  {'  ' * indent}{node.get('name')}"
        f" ({node.get('service', '') or '-'}){mark}"
    ]
    for child in node.get("children", []):
        lines.extend(render_tree(child, indent + 1))
    # A serving.step span has no children; its phases tile it.
    for name, _offset, phase_s in (node.get("attrs") or {}).get(
        "phases", ()
    ):
        lines.append(
            f"{phase_s * 1e3:9.3f}ms  {'  ' * (indent + 1)}{name}"
        )
    return lines


def device_ops_by_scope(dump, program: str, scope: str):
    """A ``trace_dump.json``'s device ops that ran inside a launch of
    ``program`` under the named scope ``scope`` (a component of the op's
    ``op_name`` path), folded by (op kind, the path below the scope):
    ``{"launches", "ms_per_launch", "rows": [{"kind", "under",
    "ms_per_launch", "ops_per_launch"}]}``, largest first."""
    launches, dur, count = 0, collections.Counter(), collections.Counter()
    for plane in dump.get("planes", {}).values():
        mods = sorted(
            (start, start + length) for name, start, length
            in plane.get("XLA Modules", ())
            if name.split("(")[0] == program
        )
        launches += len(mods)
        starts = [m[0] for m in mods]
        for _name, start, length, path, kind in plane.get("XLA Ops", ()):
            i = bisect.bisect_right(starts, start) - 1
            parts = path.split("/")
            if i < 0 or start >= mods[i][1] or scope not in parts:
                continue
            key = (kind, "/".join(parts[parts.index(scope) + 1:]))
            dur[key] += length
            count[key] += 1
    per = 1e6 * max(launches, 1)          # ns -> ms a launch
    return {
        "launches": launches,
        "ms_per_launch": sum(dur.values()) / per,
        "rows": [
            {"kind": kind, "under": under, "ms_per_launch": ns / per,
             "ops_per_launch": count[kind, under] / max(launches, 1)}
            for (kind, under), ns in dur.most_common()
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("files", nargs="+", help="span JSONL files")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest-span count (default mode)")
    ap.add_argument("--name", help="filter spans by name")
    ap.add_argument("--summary", action="store_true",
                    help="per-name latency table")
    ap.add_argument("--verbs", action="store_true",
                    help="per-verb latency table from master.<verb> "
                    "server spans (cross-check vs master_rpc_seconds)")
    ap.add_argument("--serving", action="store_true",
                    help="per-phase latency table from serving.* "
                    "request spans (queue/prefill/migrate/decode + "
                    "draft/verify split, with request-time share)")
    ap.add_argument("--steps", action="store_true",
                    help="per-phase latency table from serving.step "
                    "spans (share of step time) + the steps' counts")
    ap.add_argument("--setup", action="store_true",
                    help="a process's start: compile / cache-load / "
                    "trace seconds by program from compile.* spans, the "
                    "cache directory at start, engine build and "
                    "warm-up phases")
    ap.add_argument("--stalls", action="store_true",
                    help="the steps that took far longer than their "
                    "like, each with its cause, and the seconds lost "
                    "by cause")
    ap.add_argument("--step-name", default=stalls.SERVING_STEP,
                    help="the step span --stalls judges (train.step "
                    "for a trainer's sink)")
    ap.add_argument("--trace",
                    help="print one trace's tree + critical path")
    ap.add_argument("--device-ops", metavar="SCOPE",
                    help="the files are trace_dump.json of traced "
                    "benchmark runs: device ops under this named scope "
                    "of --program, by op")
    ap.add_argument("--program", default="jit_step",
                    help="the program --device-ops reads (jit_step)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ns = ap.parse_args(argv)
    if ns.device_ops:
        for path in ns.files:
            with open(path) as f:
                table = device_ops_by_scope(
                    json.load(f), ns.program, ns.device_ops
                )
            if ns.json:
                print(json.dumps(table))
                continue
            print(f"{path}: {ns.device_ops} of {ns.program}, "
                  f"{table['launches']} launches, "
                  f"{table['ms_per_launch']:.3f} ms a launch")
            for r in table["rows"][:ns.top]:
                print(f"{r['ms_per_launch']:9.3f}ms {r['ops_per_launch']:7.1f}"
                      f" ops  {r['kind']:<10} {r['under']}")
        return 0
    spans = load_spans(ns.files)
    if not spans:
        print("no spans found", file=sys.stderr)
        return 1

    if ns.trace:
        roots = build_trees(
            [s for s in spans if s.get("trace_id") == ns.trace]
        )
        path = critical_path(spans, ns.trace)
        if ns.json:
            print(json.dumps({"tree": roots, "critical_path": path}))
            return 0
        for root in roots:
            print("\n".join(render_tree(root)))
        print("\ncritical path:")
        for hop in path:
            print(
                f"  {hop['dur_s'] * 1e3:9.3f}ms "
                f"(self {hop['self_s'] * 1e3:8.3f}ms)  {hop['name']}"
            )
        return 0

    if ns.stalls:
        table = stalls.summary(spans, step_name=ns.step_name)
        if not table["steps"]:
            print(f"no {ns.step_name} span has a period to judge",
                  file=sys.stderr)
            return 1
        if ns.json:
            print(json.dumps(table))
        else:
            _print_stalls(table, spans)
        return 0

    if ns.setup:
        table = setup_summary(spans)
        if not table["programs"] and not table["engine"]:
            print("no compile.* or engine set-up spans found",
                  file=sys.stderr)
            return 1
        if ns.json:
            print(json.dumps(table))
        else:
            _print_setup(table)
        return 0

    if ns.summary or ns.verbs or ns.serving or ns.steps:
        counts = None
        if ns.verbs:
            rows = verb_summary(spans)
        elif ns.serving:
            rows = serving_summary(spans)
        elif ns.steps:
            table = step_summary(spans)
            rows, counts = table["phases"], table["counts"]
        else:
            # (the armed Tracer's own watcher spans are --stalls')
            rows = summarize(
                [s for s in spans if s.get("name") not in host_watch.NAMES]
            )
        if ns.verbs and not rows:
            print("no master.<verb> server spans found", file=sys.stderr)
            return 1
        if (ns.serving or ns.steps) and not rows:
            print("no serving.* spans found", file=sys.stderr)
            return 1
        if ns.json:
            print(json.dumps(table if ns.steps else rows))
            return 0
        share_hdr = f"{'share%':>8}" if ns.serving or ns.steps else ""
        print(f"{'name':<28}{'count':>7}{'err':>5}{'mean_ms':>10}"
              f"{'p50_ms':>10}{'p95_ms':>10}{'max_ms':>10}{share_hdr}")
        for r in rows:
            share = f"{r['share_pct']:>8.2f}" if share_hdr else ""
            print(
                f"{r['name']:<28}{r['count']:>7}{r['errors']:>5}"
                f"{r['mean_s'] * 1e3:>10.3f}{r['p50_s'] * 1e3:>10.3f}"
                f"{r['p95_s'] * 1e3:>10.3f}{r['max_s'] * 1e3:>10.3f}"
                f"{share}"
            )
        if counts is not None:
            print("  ".join(f"{k}={v}" for k, v in counts.items()))
        return 0

    rows = slowest(spans, top=ns.top, name=ns.name)
    if ns.json:
        print(json.dumps(rows))
        return 0
    for r in rows:
        print(
            f"{r['dur_s'] * 1e3:9.3f}ms  {r.get('name'):<24} "
            f"trace={r.get('trace_id')} status={r.get('status')} "
            f"attrs={r.get('attrs')}"
        )
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except BrokenPipeError:
        # Piped into head/less and the reader closed: not an error.
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        raise SystemExit(0)
