"""Prefix cache: ``prefix_hit_token_share_pct``'s quantity (of the prompt
tokens of the requests admitted in the window, the share the prefix cache
supplied from a boundary that held a state snapshot) for a program of
delta-rule and full layers; the accepted readers' lists are pinned to one
cell each (PERF.md section 7), so this one calls
``conv_prefix_hit_token_share_pct``'s function."""

from benchmark import delta_scopes
from benchmark.layer_metrics import conv_prefix_hit_token_share_pct


def read(facts):
    if not delta_scopes.is_cell(facts):
        return None
    return conv_prefix_hit_token_share_pct.read(facts)
