"""A statistic over the program's own spans in the profiler trace: the host
events of exactly this ``name`` (``hvd:serve.decode``; the program opens
them with ``jax.profiler.TraceAnnotation``), on any host line, that end
inside the traced window.  ``mean_ms``: their mean length.  None where the
trace holds no such span (a program that does not name this work)."""

from perfbench import trace as tr


def spans(trace, names, window=None):
    """``(start, end)`` of every host event whose name is one of ``names``
    and, given a window, that ends inside it."""
    return [(e.start, e.end) for evs in trace.host.values() for e in evs
            if e.name in names
            and (window is None or window[0] <= e.end <= window[1])]


def read(run, name, stat):
    t = run.facts.get("trace")
    if t is None:
        return None
    found = spans(t, (name,), run.facts.get("trace_window"))
    if not found:
        return None
    if stat == "mean_ms":
        return 1e-6 * tr.total(found) / len(found)
    raise ValueError(stat)
