"""What the readers of the engine's ``serving.step`` spans share.

A traced serve run returns every span its armed ``Tracer`` finished as
``facts["spans"]``. One ``serving.step`` span covers one engine
iteration: ``mono`` / ``ts`` its start (program clock / epoch), ``dur_s``,
and in ``attrs`` the contiguous ``phases`` ``[[name, offset_s, dur_s],
...]`` with the counts ``n_decoding``, ``prefill_tokens`` and so on
(``dlrover_tpu/serving/engine.py``, ``STEP_PHASES``). A program without
these spans (the parent of the PR that added them) gives every reader
here nothing to read, and each returns ``None``.

Program-clock readers use the spans that END inside the timed window,
the seconds ``serve_tokens_per_s`` counts: the runner's ramp and its
profiler session, which slows the host, both end before it opens.
Facts that carry no window (hand-built ones) are read whole.

The device trace has a clock of its own. The profiler writes its
events in nanoseconds since ITS SESSION STARTED (it subtracts the
session's start, kept only in the xplane's "Task Environment" plane,
which ``trace_reduce.dump_xplane`` does not copy), so no span's epoch
``ts`` can be laid on the dump directly. ``profile_clock`` finds the
session among the step spans from the dump itself: every
``bench.engine_step`` annotation of ``runners/serve.py`` brackets
exactly one ``serving.step`` span, so the run of spans whose durations
match the annotations' is the session, and the median of (annotation
start - span start) over those pairs is the session's origin on the
span clock, their spread its error.
"""

import statistics

STEP = "serving.step"
ENGINE_STEP = "bench.engine_step"
LAUNCHES = ("prefill_launch", "decode_launch")
# An annotation outlasts the span it brackets by the span's own emission
# (0.08 ms into a ring, 0.13 ms into a JSONL sink on the v5e's host),
# much the same every step. A pairing is believed when that excess is
# under OVERHEAD_NS and the durations agree this closely (median) once
# it is taken off.
MATCH_NS = 1e5
OVERHEAD_NS = 1e6


def window(facts):
    """(lo, hi) of the timed window in epoch seconds; unbounded when
    the facts do not say."""
    ctx, seconds = facts.get("ctx"), facts.get("window", {}).get("seconds")
    setup_s = facts.get("end_to_end", {}).get("setup_s")
    if not ctx or seconds is None or setup_s is None:
        return float("-inf"), float("inf")
    lo = ctx["t_start"] + setup_s
    return lo, lo + seconds


def ending_in_window(facts, name):
    """Finished ``name`` spans whose end lies in the window, by start."""
    lo, hi = window(facts)
    out = [
        s for s in facts.get("spans") or ()
        if s["name"] == name and s.get("dur_s") is not None
        and lo <= s["ts"] + s["dur_s"] <= hi
    ]
    out.sort(key=lambda s: s["mono"])
    return out


def phase_s(step, names):
    return sum(p[2] for p in step["attrs"]["phases"] if p[0] in names)


def launched(step):
    return step["status"] == "ok" and any(
        p[0] in LAUNCHES for p in step["attrs"]["phases"]
    )


def steps(facts):
    """The window's steps that launched a device program."""
    return [s for s in ending_in_window(facts, STEP) if launched(s)]


def neighbours(facts):
    """(step k, step k+1) for every two consecutive steps of the
    window that both launched."""
    found = steps(facts)
    return [
        (a, b) for a, b in zip(found, found[1:])
        if b["attrs"]["idx"] == a["attrs"]["idx"] + 1
    ]


def median_ms(seconds):
    seconds = list(seconds)
    return 1e3 * statistics.median(seconds) if seconds else None


def phase_ms_p50(facts, names):
    """Median over the window's steps of the milliseconds a step
    spends in the phases ``names``."""
    return median_ms(phase_s(s, names) for s in steps(facts))


def weighted_quantile(pairs, q):
    """The smallest value at which the weights of the (value, weight)
    pairs up to it reach ``q`` of their sum."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    total = sum(w for _, w in pairs)
    reached = 0.0
    for value, weight in pairs:
        reached += weight
        if reached >= q * total:
            return value
    return None


def profile_clock(facts):
    """Where the profiler session lies among the step spans and how the
    two clocks map: ``{"steps": the spans the session covers, in order,
    "base_ts", "origin_ns", "spread_ns", "pairs"}``, with
    ``profile_ns(t) = (t - base_ts) * 1e9 + origin_ns`` for an epoch
    ``t``. ``None`` without annotations or step spans, or when no run
    of spans matches the annotations' durations."""
    host = sorted(
        (r for r in (facts.get("dump") or {}).get("host", ())
         if r[0] == ENGINE_STEP),
        key=lambda r: r[1],
    )
    spans = sorted(
        (s for s in facts.get("spans") or () if s["name"] == STEP),
        key=lambda s: s["mono"],
    )
    n = len(host)
    if n < 2 or len(spans) < n:
        return None
    span_ns = [s["dur_s"] * 1e9 for s in spans]
    best, best_score = None, float("inf")
    for k in range(len(spans) - n + 1):
        excess = [host[i][2] - span_ns[k + i] for i in range(n)]
        overhead = statistics.median(excess)
        cost = statistics.median(abs(e - overhead) for e in excess)
        if not (cost < MATCH_NS and 0 <= overhead < OVERHEAD_NS):
            continue
        if overhead + cost < best_score:  # the tightest bracket wins
            best, best_score = k, overhead + cost
    if best is None:
        return None
    covered = spans[best:best + n]
    base_ts = covered[0]["ts"]
    residuals = [
        r[1] - (s["ts"] - base_ts) * 1e9 for r, s in zip(host, covered)
    ]
    q1, _, q3 = statistics.quantiles(residuals, n=4)
    return {
        "steps": covered,
        "base_ts": base_ts,
        "origin_ns": statistics.median(residuals),
        "spread_ns": q3 - q1,
        "pairs": n,
    }
