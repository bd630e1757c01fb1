"""Flash checkpoint: ``Checkpointer.load_checkpoint`` ->
``block_until_ready(state)`` in the restarted worker (shm image to
device)."""

from benchmark import common


def read(facts):
    done = common.by_event(facts["events"], "restored", incarnation=1)
    return done[0]["seconds"] if done else None
