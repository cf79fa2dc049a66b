"""The q-th percentile of the samples the driver kept under a name."""

from benchmark import stats


def read(run, spec):
    values = run.samples.get(spec["samples"])
    return stats.percentile(values, spec["q"]) if values else None
