"""Device time of the collective ops in the trace.  ``ms_per_step``: their
merged duration per traced step, mean over chips.  ``exposed_pct``: the
share of it during which no other op runs on that chip."""

from perfbench import trace as tr


def read(run, what):
    t, steps = run.facts.get("trace"), run.facts.get("steps")
    if t is None or not steps:
        return None
    w = run.facts.get("trace_window")
    per_chip = [tr.collectives(t, chip, w) for chip in sorted(t.ops)]
    per_chip = [c for c in per_chip if c["count"]]
    if not per_chip:
        return None
    total = sum(c["seconds"] for c in per_chip)
    if what == "ms_per_step":
        return 1e3 * total / len(per_chip) / steps
    if what == "exposed_pct":
        return 100.0 * sum(c["exposed_seconds"] for c in per_chip) / total
    raise ValueError(what)
