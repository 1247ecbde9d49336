"""A number the job measured itself and left in ``run.facts``."""


def read(run, key):
    v = run.facts.get(key)
    return None if v is None else float(v)
