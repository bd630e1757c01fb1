"""Flash checkpoint: the stall of the run's FIRST save, which also
creates the shm segment and touches its pages for the first time."""

from benchmark import common


def read(facts):
    saved = common.by_event(facts["events"], "saved", incarnation=0)
    return saved[0]["seconds"] if saved and saved[0]["ok"] else None
