"""From a profiler trace to numbers: device busy and idle time, the time
under each named scope, kernel times, and the idle gaps labelled by what
the host was doing in them.

Two steps, so that the arithmetic can be checked on a small recorded
trace (``tests/benchmark/data/``) with no profiler and no chip:

- ``dump_xplane(path, scopes)`` reads the ``.xplane.pb`` the JAX
  profiler wrote (``jax.profiler.ProfileData``, nothing but JAX) into a
  plain dict: for every device plane its "XLA Ops" events ``[instruction,
  start_ns, dur_ns, scope, category]`` and its "XLA Modules" events, and
  from the host plane the ``bench.*`` ``TraceAnnotation``s the runners
  place around batch build, step call, loss fetch and the engine's
  ``step()``. On this JAX an op event is named by its whole HLO
  instruction text and carries no ``tf_op``; the instruction's name and
  opcode are cut from that text, and its scope (the ``jax.named_scope``
  path) is looked up in ``scopes``, which ``scopes_from_hlo`` reads from
  the compiled program's own text (``metadata={op_name=...}``).
- ``reduce(dump)`` is pure arithmetic over that dict.

The host's and the device's clocks in one trace differ by some tenths
of a millisecond (the first chip trace shows a step's module starting
0.44 ms before the call that launched it), so a gap's label is good to
about that.

Copied in idea from ``dlrover_tpu/tpu_timer/xla_capture.py``
(``parse_op_profile``, ``bucket_by_scope``) and extended with the idle
share and the gap attribution it lacks; the original stays where it is
(PERF.md, Open questions).
"""

import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# Control-flow envelopes contain their body ops, which the trace also
# lists one by one: counting both would count every scan body twice.
ENVELOPES = ("while", "conditional", "call")
KERNEL = "custom-call:tpu_custom_call"  # a Pallas (Mosaic) kernel
SCOPES = ("attn", "mlp", "vocab", "optimizer")
_SCOPE_RE = {
    # a path component, or the argument of jvp(...) / transpose(jvp(...))
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}


_INSTRUCTION = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"',
    re.MULTILINE,
)


def parse_event_name(text):
    """(instruction name, category) of an op event named by its HLO
    text: ``%fusion.3 = f32[8]{0} fusion(...)`` -> ("fusion.3",
    "fusion"); a custom call's category carries its target."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:80], ""
    op = _OPCODE.search(text, m.end())
    category = op.group(1) if op else ""
    if category == "custom-call":
        target = _TARGET.search(text)
        if target:
            category += ":" + target.group(1)
    return m.group(1), category


def scopes_from_hlo(hlo_text):
    """instruction name -> op_name (the named-scope path), from a
    compiled program's text."""
    return dict(_OP_NAME.findall(hlo_text))


def dump_xplane(path, scopes=None):
    from jax.profiler import ProfileData

    scopes = scopes or {}
    data = ProfileData.from_file(path)
    planes, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rows = []
                    for e in line.events:
                        inst, category = parse_event_name(e.name)
                        rows.append([
                            inst, int(e.start_ns), int(e.duration_ns),
                            scopes.get(inst, ""), category,
                        ])
                    lines[OPS_LINE] = rows
                elif line.name == MODULES_LINE:
                    lines[MODULES_LINE] = [
                        [e.name[:80], int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
            if lines:
                planes[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(
                            [e.name, int(e.start_ns), int(e.duration_ns)]
                        )
    host.sort(key=lambda r: r[1])
    return {"planes": planes, "host": host}


def union(intervals):
    """Merged, sorted ``[lo, hi]`` intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _clip(intervals, lo, hi):
    return [
        [max(a, lo), min(b, hi)] for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


def _gaps(busy, lo, hi):
    out, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            out.append([cursor, a])
        cursor = max(cursor, b)
    if hi > cursor:
        out.append([cursor, hi])
    return out


def _label(gap, host):
    """The innermost host span that was open for at least half of the
    gap ("none": the host had no bench.* span open for most of it)."""
    lo, hi = gap
    best = None
    for name, start, dur in host:
        o = min(hi, start + dur) - max(lo, start)
        if o * 2 >= hi - lo and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "none"


def scope_of(scope):
    for s in SCOPES:
        if _SCOPE_RE[s].search(scope):
            return s
    return "unscoped"


def module_name(name):
    """jit_step(1388760915...) -> jit_step."""
    return name.split("(", 1)[0]


def base_name(name):
    """fusion.123 -> fusion; keeps kernels' own names whole."""
    return re.sub(r"[.\d]+$", "", name) or name


def reduce(dump, top=10):
    """See the module docstring. Times in seconds. ``window_s`` is the
    span of the host's bench.* annotations when there are any (the
    steady steps the runner meant to trace), else of the device events;
    ``busy_s`` is the union of device-op intervals inside it, averaged
    over the device planes that ran anything."""
    host = dump.get("host", [])
    planes = {
        name: lines for name, lines in dump.get("planes", {}).items()
        if lines.get(OPS_LINE) or lines.get(MODULES_LINE)
    }
    spans = [
        (r[1], r[1] + r[2]) for lines in planes.values()
        for r in lines.get(OPS_LINE) or lines.get(MODULES_LINE)
    ]
    if not spans:
        return None
    if host:
        lo = min(r[1] for r in host)
        hi = max(r[1] + r[2] for r in host)
    else:
        lo = min(s[0] for s in spans)
        hi = max(s[1] for s in spans)
    busy_ns, gap_s, op_s, scope_s, kernel_s = [], {}, {}, {}, {}
    module_s = {}
    for lines in planes.values():
        rows = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        busy = _clip(union([r[1], r[1] + r[2]] for r in rows), lo, hi)
        if not busy:
            continue
        busy_ns.append(sum(b - a for a, b in busy))
        for gap in _gaps(busy, lo, hi):
            label = _label(gap, host)
            gap_s[label] = gap_s.get(label, 0) + (gap[1] - gap[0]) / 1e9
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda r: r[1])
        for name, start, dur in modules:
            if start + dur > lo and start < hi:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0) + dur / 1e9
        several = len({module_name(r[0]) for r in modules}) > 1
        cursor = 0
        for r in sorted(lines.get(OPS_LINE, []), key=lambda r: r[1]):
            name, start, dur, scope, cat = r
            if cat in ENVELOPES or start + dur <= lo or start >= hi:
                continue
            s = scope_of(scope)
            scope_s[s] = scope_s.get(s, 0) + dur / 1e9
            key = base_name(name)
            if cat == KERNEL:
                kernel_s[key] = kernel_s.get(key, 0) + dur / 1e9
            if s != "unscoped":
                key = s + ":" + key
            elif several:
                # No scope to tell by: say which program the op ran in.
                while cursor + 1 < len(modules) and (
                    modules[cursor][1] + modules[cursor][2] <= start
                ):
                    cursor += 1
                m_name, m_start, m_dur = modules[cursor]
                if m_start <= start < m_start + m_dur:
                    key = module_name(m_name) + ":" + key
            op_s[key] = op_s.get(key, 0) + dur / 1e9
    if not busy_ns:
        return None
    n = len(busy_ns)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / n / 1e9
    leaf_s = sum(scope_s.values())

    def ranked(d):
        return [
            [k, v / n] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "n_planes": n,
        "scope_s": {k: v / n for k, v in scope_s.items()},
        "unscoped_share": (
            scope_s.get("unscoped", 0.0) / leaf_s if leaf_s > 0 else None
        ),
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "module_s": {k: v / n for k, v in module_s.items()},
        "device_ops": ranked(op_s),
        "idle_gaps": ranked(gap_s),
    }


def crop(dump, max_ns):
    """The dump's first ``max_ns`` nanoseconds (what the tests keep)."""
    starts = [r[1] for r in dump["host"]] + [
        r[1] for lines in dump["planes"].values()
        for rows in lines.values() for r in rows
    ]
    if not starts:
        return dump
    end = min(starts) + max_ns
    return {
        "planes": {
            name: {
                line: [r for r in rows if r[1] + r[2] <= end]
                for line, rows in lines.items()
            }
            for name, lines in dump["planes"].items()
        },
        "host": [r for r in dump["host"] if r[1] + r[2] <= end],
    }
