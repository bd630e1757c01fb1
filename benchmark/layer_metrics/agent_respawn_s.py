"""Launcher and agent: the parent's SIGKILL of the worker -> the new
worker's ``boot`` event (first line of the restarted script), both on
one host's wall clock."""

from benchmark import common


def read(facts):
    boot = common.by_event(facts["events"], "boot", incarnation=1)
    if not boot or facts.get("t_kill") is None:
        return None
    return boot[0]["t"] - facts["t_kill"]
