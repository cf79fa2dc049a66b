"""A share of the traced window on the device, in percent: 'idle' is 1
minus the union of device-op intervals (mean over the chips used);
'collective' is the union of collective ops on device 0."""

from benchmark import xplane


def read(run, spec):
    if run.trace is None or not run.trace["devices"]:
        return None
    window = (run.trace_window[1] - run.trace_window[0]) / 1e9
    if spec["what"] == "idle":
        return 100.0 * (1.0 - xplane.busy_seconds(run.trace) / window)
    return 100.0 * xplane.collective_seconds(run.trace["devices"][0]) / window
