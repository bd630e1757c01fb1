"""What the readers of set-up share: the cut at the timed window's
start, over the program's own account of the seconds before it.

The account is the program's (``dlrover_tpu/common/compile_cache.py``):

- ``compile_log()``, read IN PROCESS (``run.py`` calls the readers in
  the runner's process, and the train runners arm no Tracer): one
  record a compile-or-load (``backend_compile`` with ``cache`` ``hit`` /
  ``written`` / ``uncached``, ``requested`` and the hit's
  ``retrieval_s``), a cache load (``cache_load``), an outermost trace or
  lowering (``trace``, ``lower``), each with JAX's own wall start
  ``ts``, ``seconds`` and ``fun_name``. It listens from the import of
  that module, which every runner makes before its first compile, the
  serve runners' weight program included. Hand-built facts carry theirs
  as ``facts["compile_log"]``.
- ``setup_summary()``, the one aggregation of such records (seconds by
  program, how the cache answered): the scalars here are its totals
  over the records before the window, so the table and the metrics
  cannot disagree.
- ``facts["kv_stats"]``'s ``engine_build_s`` / ``warmup_s``, the
  engine's own floats, in timed and traced runs of both serve cells;
  ``facts["spans"]``'s ``serving.engine_build`` / ``serving.warmup``,
  where the runner armed its Tracer before it built the engine, give
  the table their phases.

"Before the window" is ``ts <= ctx["t_start"] + setup_s``: the serve
runners compile their reference AFTER the window, and that is not
set-up. Facts that carry no window are read whole. A program without
the account (the parent of the PR that added it) gives every reader
here nothing to read, and each returns ``None``.

``table`` is the breakdown behind the scalars, for the builder and the
next issue's author. ``run.py`` calls nothing but readers, so ONE of
them, ``setup_compile_s``, leaves it in the run's ``events`` (so in
``traced.json``) as the event ``setup_table``.
"""

TABLE_EVENT = "setup_table"
TOP_FUN_NAMES = 40


def window_start(facts):
    """Epoch second at which the timed window opened, or +inf."""
    ctx = facts.get("ctx")
    setup_s = (facts.get("end_to_end") or {}).get("setup_s")
    if not ctx or setup_s is None:
        return float("inf")
    return ctx["t_start"] + setup_s


def _account():
    """The program's module, or None where it keeps no account."""
    try:
        from dlrover_tpu.common import compile_cache
    except ImportError:
        return None
    if not (hasattr(compile_cache, "compile_log")
            and hasattr(compile_cache, "setup_summary")):
        return None
    return compile_cache


def compile_log(facts):
    """``{"header", "records"}`` of this process, or None."""
    account = _account()
    if account is None:
        return None
    log = facts.get("compile_log")
    return log if log is not None else account.compile_log()


def summary(facts, top=None):
    """The program's ``setup_summary`` of what began before the window
    opened, or None without an account."""
    log = compile_log(facts)
    if log is None:
        return None
    cut = window_start(facts)
    return _account().setup_summary(
        [r for r in log["records"] if r["ts"] <= cut],
        [s for s in facts.get("spans") or () if s["ts"] <= cut],
        cache=log["header"], top=top,
    )


def _total(facts, key):
    found = summary(facts)
    return None if found is None else found["totals"][key]


def compile_s(facts):
    """Seconds compiling before the window: compile-or-load less the
    hits' retrievals, which ``cache_load_s`` counts."""
    return _total(facts, "compile_s")


def cache_load_s(facts):
    return _total(facts, "cache_load_s")


def trace_lower_s(facts):
    """Outermost traces and lowerings only: the program drops the
    events of jitted functions traced inside another's trace."""
    return _total(facts, "trace_lower_s")


def cache_hit_pct(facts):
    """Hits over the compiles that asked the cache; None when none
    did. By count: a program under JAX's thresholds (1 s of compile
    by default) is never stored, asks every time and never hits, so a
    warm run reads the share of its programs that are large enough to
    keep, not 100."""
    found = summary(facts)
    if found is None or not found["totals"]["requested"]:
        return None
    totals = found["totals"]
    return 100.0 * totals["hit"] / totals["requested"]


def engine_build_s(facts):
    """Construction plus warm-up as the engine timed them: its own
    floats in ``kv_stats``, the same seconds its spans carry."""
    stats = facts.get("kv_stats") or {}
    if "engine_build_s" not in stats:
        return None
    return stats["engine_build_s"] + stats.get("warmup_s", 0.0)


def table(facts):
    """Set-up by program (which tells the benchmark's reference
    programs from the step and the engine programs) and by phase, the
    cache directory as the program found it, and what the log holds
    from the window on (inside it: nothing, or the run compiled)."""
    found = summary(facts, top=TOP_FUN_NAMES)
    if found is None:
        return None
    cut = window_start(facts)
    seconds = (facts.get("window") or {}).get("seconds")
    end = cut + seconds if seconds is not None else float("inf")
    records = compile_log(facts)["records"]
    found["records"] = {
        "before_window": sum(r["ts"] <= cut for r in records),
        "in_window": sum(cut < r["ts"] <= end for r in records),
        "after_window": sum(r["ts"] > end for r in records),
    }
    found["after_window_backend_s"] = sum(
        r["seconds"] for r in records
        if r["ts"] > end and r["event"] == "backend_compile"
    )
    return found


def leave_table(facts):
    """Put ``table`` among the run's events, once."""
    events = facts.get("events")
    if events is None or any(
        e.get("event") == TABLE_EVENT for e in events
    ):
        return
    found = table(facts)
    if found is not None:
        events.append(dict(found, event=TABLE_EVENT))
