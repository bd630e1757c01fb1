"""Where JAX's persistent compilation cache lives, and what every
compile of this process cost.

A restarted worker (and every process of one job) should compile from
cache, and the directory is part of the cache key's context: a path
that moves never hits. So the placement rule is:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  set in code.
- otherwise: one fixed, git-ignored directory inside the checkout —
  no pid, time or tempfile name in the path.

Importing this module starts the process's compile account: listeners
on ``jax.monitoring`` turn JAX's own compile events into a bounded log
(``compile_log()``) and, with a Tracer armed, retrospective ``local``
spans on JAX's own wall reads. The account opens at the import, not at
``enable_compile_cache()``, so that a program compiled BEFORE the cache
has a directory (a runner's weight program ahead of the engine's
constructor) is in it, as ``uncached``: that is the compile the account
is there to find. After warm-up nothing compiles, so the steady state
pays nothing; an event costs a few microseconds.

What is recorded, and which sums nest:

- ``compile.backend`` / record ``backend_compile``: JAX's
  ``backend_compile_duration``, which wraps ``compile_or_get_cached``.
  On a persistent-cache HIT it therefore CONTAINS the retrieval below
  (read, decompress, deserialize, load onto the device) and little
  else: seconds compiled are the backend seconds less the retrieval
  seconds of the same record (``retrieval_s``). ``cache`` says how it
  ended: ``hit``, ``written`` (compiled, entry stored) or ``uncached``
  (compiled and not stored: no directory, the cache switched off, or
  under JAX's size / compile-time thresholds; JAX fires its
  ``cache_misses`` event only when an entry is written). ``requested``:
  it asked the cache (``compile_requests_use_cache``; fired whenever
  the cache is enabled, a directory or not).
- ``compile.cache_load`` / record ``cache_load``:
  ``cache_retrieval_time_sec``, fired for hits only, inside the
  backend span of the same ``fun_name``; ``saved_s`` is JAX's
  ``compile_time_saved_sec`` (the stored compile time less the
  retrieval).
- ``compile.trace_lower`` / records ``trace`` and ``lower``:
  ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``,
  OUTERMOST only: a jitted function traced inside another's trace
  (most of ``jax.numpy``) fires its own event inside its caller's, and
  those are dropped, not logged and not summed. A trace can still
  contain a backend compile (a constant computed eagerly while
  tracing), so trace-lower seconds and backend seconds may overlap;
  backend and retrieval never do beyond the nesting above.

``setup_summary`` is the one aggregation of these records (seconds by
program, how the cache answered, the engines' build and warm-up
phases): ``tools/trace_query.py --setup`` calls it on a sink's spans,
the benchmark's set-up readers on the log.
"""

import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional

import jax
from jax import monitoring

from dlrover_tpu.observability import tracing

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

LOG_CAPACITY = 1024

_BACKEND = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_WRITES = "/jax/compilation_cache/cache_misses"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_STAGES = {_TRACE: "trace", _LOWER: "lower"}
_SPAN_EVENTS = {"compile.backend": "backend_compile",
                "compile.cache_load": "cache_load"}
_ENGINE_SPANS = ("serving.engine_build", "serving.warmup")


def compile_cache_dir() -> str:
    """The directory the persistent cache uses in this environment."""
    return os.environ.get(CACHE_DIR_ENV) or _CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Point JAX at ``compile_cache_dir()``; call before the first
    compile that should be kept. The first call also looks at the
    directory once (``compile_log()``'s header). Returns the directory
    in use."""
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update(
            "jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR
        )
    _account.scan_once(compile_cache_dir())
    return compile_cache_dir()


def compile_log() -> Dict:
    """``{"header": {...}, "records": [...]}``: since when the account
    listens, the cache directory as the first ``enable_compile_cache()``
    found it (``dir``, ``entries``, ``bytes``: "empty at start" and
    "full, under another key" are different faults; absent until that
    call), how many records the bound has dropped, and the newest
    ``LOG_CAPACITY`` records ``{ts (epoch), mono, event, seconds,
    fun_name, ...}`` with ``event`` one of ``backend_compile``
    (+ ``cache``, ``requested``, ``retrieval_s``), ``cache_load``
    (+ ``saved_s``), ``trace``, ``lower``."""
    return _account.snapshot()


def records_from_spans(spans: Iterable[Dict]) -> List[Dict]:
    """The ``compile.*`` spans of a ring or a JSONL sink, in the log's
    record shape (a span's attrs are its record's other fields)."""
    out = []
    for s in spans:
        name, attrs = s.get("name", ""), s.get("attrs") or {}
        if not name.startswith("compile.") or s.get("dur_s") is None:
            continue
        event = _SPAN_EVENTS.get(name) or attrs.get("stage", "trace")
        out.append(dict(attrs, ts=s["ts"], event=event,
                        seconds=s["dur_s"]))
    return out


def setup_summary(records: Iterable[Dict], spans: Iterable[Dict] = (),
                  cache: Optional[Dict] = None,
                  top: Optional[int] = None) -> Dict:
    """A process's start from its own account. ``programs``: one row
    per ``fun_name`` of ``records`` (``jit(f)``, as lowering and
    compile name a program, and ``f``, as its trace does, are one row)
    with the seconds it COMPILED (compile-or-load less the hit's
    retrieval inside it), LOADED from the persistent cache
    (``saved_s``: what JAX says the hits saved, so what a miss would
    have cost more) and was TRACED and LOWERED (outermost calls only),
    how many of its compiles asked the cache (``requested``) and how it
    answered (``hit`` / ``written`` / ``uncached``), slowest first;
    past ``top`` rows the rest are one ``(other)``. ``totals``: the
    rows' sums. ``cache``: the directory's entries and bytes when the
    process first looked (given, else from a ``compile.backend`` span's
    attrs). ``engine``: every ``serving.engine_build`` /
    ``serving.warmup`` span of ``spans``, by start, with its phases and
    sizes."""
    keys = ("compile_s", "cache_load_s", "saved_s", "trace_lower_s",
            "requested", "hit", "written", "uncached")
    programs: Dict[str, Dict] = {}
    for r in records:
        fun = r.get("fun_name", "")
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]
        row = programs.setdefault(
            fun, dict({"name": fun}, **dict.fromkeys(keys, 0))
        )
        if r["event"] == "backend_compile":
            row["compile_s"] += max(
                r["seconds"] - r.get("retrieval_s", 0.0), 0.0
            )
            row["requested"] += bool(r.get("requested"))
            row[r.get("cache", "uncached")] += 1
            if cache is None and "cache_entries" in r:
                cache = {"entries": r["cache_entries"],
                         "bytes": r["cache_bytes"]}
        elif r["event"] == "cache_load":
            row["cache_load_s"] += r["seconds"]
            row["saved_s"] += r.get("saved_s", 0.0)
        else:
            row["trace_lower_s"] += r["seconds"]
    rows = sorted(programs.values(), key=lambda r: -(
        r["compile_s"] + r["cache_load_s"] + r["trace_lower_s"]
    ))
    totals = {k: sum(r[k] for r in rows) for k in keys}
    if top is not None and len(rows) > top:
        rest = rows[top:]
        rows = rows[:top] + [dict(
            {k: sum(r[k] for r in rest) for k in keys},
            name="(other)", fun_names=len(rest),
        )]
    engine = sorted(
        (s for s in spans
         if s.get("name") in _ENGINE_SPANS
         and s.get("dur_s") is not None),
        key=lambda s: s["ts"],
    )
    return {
        "cache": cache or {}, "programs": rows, "totals": totals,
        "engine": [
            {"name": s["name"], "dur_s": s["dur_s"],
             "phases": [[p[0], p[2]] for p in s["attrs"]["phases"]],
             **{k: v for k, v in s["attrs"].items() if k != "phases"}}
            for s in engine
        ],
    }


def _scan(path: str):
    """(entries, bytes) of one directory level; (0, 0) when absent."""
    entries = size = 0
    try:
        with os.scandir(path) as it:
            for entry in it:
                try:
                    if entry.is_file():
                        entries += 1
                        size += entry.stat().st_size
                except OSError:
                    continue
    except OSError:
        pass
    return entries, size


class _CompileAccount:
    """The listeners' shared state. Compiles run on whatever thread
    first calls a program, so what belongs to the compile under way
    (its ``fun_name``, how the cache answered, the trace depth) is
    per thread; the log is shared."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: "deque[Dict]" = deque(maxlen=LOG_CAPACITY)
        self._header: Dict = {
            "listening_since": time.time(), "capacity": LOG_CAPACITY,
        }
        self._dropped = 0
        self._thread = threading.local()
        monitoring.register_scalar_listener(self._on_enter)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        monitoring.register_event_time_span_listener(self._on_span)

    def scan_once(self, path: str) -> None:
        with self._lock:
            if "dir" in self._header:
                return
            self._header["dir"] = path
        entries, size = _scan(path)
        with self._lock:
            self._header.update(entries=entries, bytes=size)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "header": dict(self._header, dropped=self._dropped),
                "records": [dict(r) for r in self._records],
            }

    # ---- per-thread state --------------------------------------------------

    def _state(self) -> Dict:
        state = getattr(self._thread, "state", None)
        if state is None:
            state = self._thread.state = {"depth": 0, "backend": {}}
        return state

    # ---- listeners ---------------------------------------------------------

    def _on_enter(self, event: str, _value, **kw) -> None:
        """JAX announces a timed section when it ENTERS it."""
        if event == _BACKEND:
            self._state()["backend"] = {
                "fun_name": str(kw.get("fun_name", "")),
                "cache": "uncached", "retrieval_s": 0.0,
            }
        elif event in _STAGES:
            self._state()["depth"] += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _REQUESTS:
            self._state()["backend"]["requested"] = True
        elif event == _HITS:
            self._state()["backend"]["cache"] = "hit"
        elif event == _WRITES:
            self._state()["backend"]["cache"] = "written"

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _SAVED:
            self._state()["backend"]["saved_s"] = seconds
        elif event == _RETRIEVAL:
            backend = self._state()["backend"]
            backend["retrieval_s"] = seconds
            self._record(
                "compile.cache_load", "cache_load",
                time.time() - seconds, seconds,
                fun_name=backend.get("fun_name", ""),
                saved_s=backend.get("saved_s"),
            )

    def _on_span(self, event: str, start: float, end: float,
                 **kw) -> None:
        """The section's end, with JAX's own wall reads of both."""
        seconds = max(end - start, 0.0)
        if event == _BACKEND:
            state = self._state()
            backend, state["backend"] = state["backend"], {}
            self._record(
                "compile.backend", "backend_compile", start, seconds,
                fun_name=str(kw.get("fun_name", "")),
                cache=backend.get("cache", "uncached"),
                requested=backend.get("requested", False),
                retrieval_s=backend.get("retrieval_s", 0.0),
            )
        elif event in _STAGES:
            state = self._state()
            state["depth"] = max(state["depth"] - 1, 0)
            if state["depth"]:
                return  # traced inside its caller's trace
            self._record(
                "compile.trace_lower", _STAGES[event], start, seconds,
                fun_name=str(kw.get("fun_name", "")),
            )

    def _record(self, span_name: str, event: str, start: float,
                seconds: float, **fields) -> None:
        """One log record and, armed, one span; ``start`` is epoch."""
        start_mono = time.monotonic() - (time.time() - start)
        record = {"ts": start, "mono": start_mono, "event": event,
                  "seconds": seconds}
        record.update(
            (k, v) for k, v in fields.items() if v is not None
        )
        with self._lock:
            if len(self._records) == LOG_CAPACITY:
                self._dropped += 1
            self._records.append(record)
        tracer = tracing.active_tracer()
        if tracer is not None:
            attrs = {k: v for k, v in record.items()
                     if k not in ("ts", "mono", "seconds", "event")}
            if span_name == "compile.trace_lower":
                attrs["stage"] = event
            elif span_name == "compile.backend" and (
                "entries" in self._header
            ):
                # The directory as this process found it, on every
                # compile's span: a sink has no header to keep it in.
                attrs["cache_entries"] = self._header["entries"]
                attrs["cache_bytes"] = self._header["bytes"]
            tracer.record_span(
                span_name, start_mono, start_mono + seconds,
                attrs=attrs, local=True, start_wall=start,
            )


_account = _CompileAccount()
