"""Serving engine: host time a step spends before its launches --
``admit`` (shed, admit, bind slots, prefix lookup), ``prefill_prep``
(pick the chunk, grow and privatise blocks, build the chunk array) and
``decode_prep`` (block-budget pass, active mask). Median over the
window's steps."""

from benchmark import step_spans


def read(facts):
    return step_spans.phase_ms_p50(
        facts, ("admit", "prefill_prep", "decode_prep")
    )
