"""Warm-standby worker process.

A restart pays interpreter start-up plus the framework imports before
it can even ask for the chip. On a v5e host a restarted 334M
worker spent 3.5 s importing when spawned cold and 1.0 s when adopted
(``restart_imports_s``, two runs each way, PR 21) out of a
17-31 s recovery. A standby is a pre-spawned interpreter that has already
imported jax and blocks on stdin until the agent ADOPTS it as the next
worker incarnation: the agent writes one JSON line carrying the final
environment and argv (rendezvous outcome, restart count — values that
do not exist when the standby is spawned), and the standby becomes the
worker via runpy in-process.

The standby is spawned with the worker's base environment, so
everything jax reads at import (JAX_PLATFORMS, the compile-cache
variables) is already in place; only the rendezvous values arrive late.
Importing jax registers backends but initializes none — no TPU client
exists until the adopted script first touches a device, so the standby
never contends for the chip with the live worker.

Spawned by ElasticAgent when ``WorkerSpec.warm_standby`` is set (see
agent/training.py); covered by ``tests/test_elastic_agent.py``.
"""

import json
import os
import runpy
import sys


def wait_and_exec():
    try:
        import jax  # noqa: F401 - the import IS the warm-up
    except ImportError:
        pass  # a script that needs no jax still gets a live interpreter
    line = sys.stdin.readline()
    if not line:
        # Agent closed stdin without adopting (job ended): exit clean.
        sys.exit(0)
    go = json.loads(line)
    os.environ.update(go["env"])
    sys.argv = list(go["argv"])
    if go.get("module"):
        runpy.run_module(go["module"], run_name="__main__", alter_sys=True)
    else:
        runpy.run_path(go["argv"][0], run_name="__main__")


if __name__ == "__main__":
    wait_and_exec()
