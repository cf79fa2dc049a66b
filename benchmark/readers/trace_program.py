"""Median device time, in ms, of one execution of the compiled program
whose name matches the spec's pattern (device 0, 'XLA Modules' line),
divided by the traffic parameter the spec names under 'per', if any. The
median, because the executions at the traced window's edges are cut."""

import statistics

from benchmark import xplane


def read(run, spec):
    if run.trace is None or not run.trace["devices"]:
        return None
    runs = xplane.matching(run.trace["devices"][0]["modules"], spec["match"])
    if not runs:
        return None
    per = run.traffic[spec["per"]] if "per" in spec else 1
    return 1000.0 * statistics.median(runs) / per
