"""value = scale * counters[key]."""


def read(run, spec):
    v = run.counters.get(spec["key"])
    return None if v is None else spec.get("scale", 1.0) * v
