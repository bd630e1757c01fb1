"""Flash checkpoint: bytes of the train state (leaf ``nbytes``) over the
seconds a save stalled the loop (``save_checkpoint_async`` called ->
drained): the saves ``ckpt_save_stall_s`` times, i.e. those after the
first where there are several."""

from benchmark import common


def read(facts):
    saved = common.by_event(facts["events"], "saved", incarnation=0)
    compiled = common.by_event(facts["events"], "compiled", incarnation=0)
    timed = saved[1:] or saved
    if not timed or not compiled or not all(e["ok"] for e in timed):
        return None
    seconds = sum(e["seconds"] for e in timed) / len(timed)
    return compiled[0]["state_bytes"] / 1e9 / seconds
