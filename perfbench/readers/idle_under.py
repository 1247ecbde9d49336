"""The device's idle time by what the host was doing: on the cell's first
chip, the idle intervals of the traced window (the window less the union of
``XLA Ops``) intersected with the union of the program's spans called
``names`` (with ``invert``: with what none of them covers), as a percentage
**of the window**.  Shares over disjoint sets of spans, and the inverted
share over all of them, add up to the chip's ``device_idle_pct``.  None
where the trace has no device ops or none of the spans."""

from perfbench import trace as tr


def read(run, names, invert=False):
    t, w = run.facts.get("trace"), run.facts.get("trace_window")
    if t is None or w is None or not t.ops or w[1] <= w[0]:
        return None
    named = [(e.start, e.end) for evs in t.host.values()
             for e in tr.clip(evs, w) if e.name in names]
    if not named:
        return None
    ops = tr.clip(t.ops[sorted(t.ops)[0]], w)
    idle = tr.subtract([w], tr.union((e.start, e.end) for e in ops))
    outside = tr.total(tr.subtract(idle, tr.union(named)))
    share = outside if invert else tr.total(idle) - outside
    return 100.0 * share / (w[1] - w[0])
