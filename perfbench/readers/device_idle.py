"""Idle share of the traced window: 1 - (union of device-op intervals) /
window, per chip, then the mean over the chips used; in percent."""

from perfbench import trace as tr


def read(run):
    t = run.facts.get("trace")
    if t is None:
        return None
    idle = tr.busy(t, run.facts.get("trace_window"))["idle_share"]
    if not idle:
        return None
    return 100.0 * sum(idle.values()) / len(idle)
