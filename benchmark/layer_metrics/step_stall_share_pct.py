"""Serving engine: of the timed window's seconds, the share its stalled
steps lost: the summed excess (period - the kind's median period) of
every ``serving.step`` whose period exceeds its kind's median by
``max(0.05 s, median)``, whatever the cause
(``dlrover_tpu/observability/stalls.py``)."""

from benchmark import stall_spans


def read(facts):
    return stall_spans.stall_share_pct(facts)
