"""Device time of one of the program's compiled programs: the mean length,
in ms, of the first chip's ``XLA Modules`` events (one per execution of a
program, named ``jit_<function>(<fingerprint>)``: ``jit_serve_step(...)``)
whose name contains ``match`` and that end inside the traced window.  An
event is the device's whole execution of the program, whatever the host
did meanwhile.  None where no such program ran (a program that does not
name what it compiles: ``jit__unknown``)."""

from perfbench import trace as tr


def runs(trace, match, window=None):
    """``(start, end)`` of the first chip's executions of the programs
    whose name contains ``match`` and, given a window, that end inside it."""
    if not trace.modules:
        return []
    return [(e.start, e.end) for e in trace.modules[sorted(trace.modules)[0]]
            if match in e.name
            and (window is None or window[0] <= e.end <= window[1])]


def read(run, match):
    t = run.facts.get("trace")
    if t is None:
        return None
    found = runs(t, match, run.facts.get("trace_window"))
    return 1e-6 * tr.total(found) / len(found) if found else None
