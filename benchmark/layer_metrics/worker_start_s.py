"""Worker runtime: the restarted worker's ``boot`` -> ``jax.devices()``
returned (imports, ``init_distributed``, TPU client start)."""

from benchmark import common


def read(facts):
    boot = common.by_event(facts["events"], "boot", incarnation=1)
    ready = common.by_event(facts["events"], "ready", incarnation=1)
    if not boot or not ready:
        return None
    return ready[0]["t"] - boot[0]["t"]
