"""value = scale * counters[num] / product(counters[den...])."""


def read(run, spec):
    num = run.counters.get(spec["num"])
    den = 1.0
    for key in spec["den"]:
        den *= run.counters.get(key) or 0.0
    if num is None or den == 0.0:
        return None
    return spec.get("scale", 1.0) * num / den
