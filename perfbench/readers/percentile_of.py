"""A nearest-rank percentile of a list the job left in ``run.facts``."""

from perfbench.loadgen import percentile


def read(run, key, q):
    values = run.facts.get(key)
    if not values:
        return None
    return percentile(list(values), q)
