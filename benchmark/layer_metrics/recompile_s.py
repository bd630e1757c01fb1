"""Worker runtime: the restarted worker's compile of the train step,
which must come from the persistent cache (a miss fails ``correct``)."""

from benchmark import common


def read(facts):
    done = common.by_event(facts["events"], "compiled", incarnation=1)
    return done[0]["seconds"] if done else None
