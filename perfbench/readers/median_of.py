"""The median of a list the job left in ``run.facts``."""

import statistics


def read(run, key):
    values = run.facts.get(key)
    return statistics.median(values) if values else None
