"""Serving engine: from the end of step k's ``decode_fetch`` (the host
has the step's tokens; nothing is queued on the device) to the start of
step k+1's first ``*_launch`` phase, on the program's own clock: the
floor of the device's idle gap between two steps. Median over the
window's consecutive steps."""

from benchmark import step_spans


def read(facts):
    gaps = []
    for a, b in step_spans.neighbours(facts):
        fetched = [
            p[1] + p[2] for p in a["attrs"]["phases"]
            if p[0] == "decode_fetch"
        ]
        if not fetched:
            continue  # a step of prefill alone left the device busy
        launch = next(
            p[1] for p in b["attrs"]["phases"]
            if p[0] in step_spans.LAUNCHES
        )
        gaps.append(b["mono"] + launch - (a["mono"] + fetched[-1]))
    return step_spans.median_ms(gaps)
