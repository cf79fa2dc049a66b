"""Self time of a host span per call, in ms: its wall time minus that of
the child spans named in the spec."""


def read(run, spec):
    count = run.span_count.get(spec["span"])
    if not count or any(c not in run.span_total for c in spec["children"]):
        return None
    own = run.span_total[spec["span"]] - sum(
        run.span_total[c] for c in spec["children"]
    )
    return 1000.0 * own / count
