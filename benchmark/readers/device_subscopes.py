"""Device time by mechanism detail, read from the programs' own scopes.

A program may name a detail under a class, ``<class>/<detail>``
(``attend/window``, ``attend/full``): ``telemetry/scopes.py``. This
reader files an op under ``<class>/<detail>`` where the innermost class
component of its name stack is followed by a component that is not the
op's own name, and under nothing else; ``device_scopes`` files the same
op under its class, as it always has. The trace is parsed and reduced by
``device_scopes``' own functions (``op_stacks``, ``reduce``), with each
instruction's stack replaced by its detail's label.

The spec's ``what`` is ``per_execution``: the median, over the whole
executions of the program whose 'XLA Modules' event matches ``match``,
of the device-0 self time of its ops under the details in ``scopes``, in
ms. A program without such a detail — one from before it was named —
gives nothing to read.
"""

from __future__ import annotations

import re

from benchmark import xplane
from benchmark.readers import device_scopes


def detail(op_name: str, classes) -> str:
    """``<class>/<detail>`` of the innermost class in ``op_name`` and the
    component after it, or "" where it has none."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        part = parts[i]
        while True:
            m = device_scopes._WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in classes:
            # the last component is the op itself, not a detail
            return f"{part}/{parts[i + 1]}" if i + 2 < len(parts) else ""
    return ""


def collect(run):
    """Device 0's ops reduced by detail, gathered once and kept on the
    run; None where there is nothing to read."""
    if hasattr(run, "_device_subscopes"):
        return run._device_subscopes
    run._device_subscopes = None
    classes = device_scopes.vocabulary()
    trace_dir = getattr(run, "_trace_dir", None)
    if (classes is None or run.trace is None or not run.trace["devices"]
            or trace_dir is None):
        return None
    path = xplane.find_xplane(str(trace_dir))
    if path is None:
        return None
    stacks, _ = device_scopes.op_stacks(path)
    # a label without "/" is one component: ``reduce`` files it as a class
    labels = {name: detail(stack, classes).replace("/", ":")
              for name, stack in stacks.items()}
    found = {label for label in labels.values() if label}
    if not found:
        return None
    run._device_subscopes = device_scopes.reduce(
        run.trace["devices"][0], run.trace_window, labels, found)
    return run._device_subscopes


def read(run, spec):
    if spec["what"] != "per_execution":
        raise ValueError(f"device_subscopes: unknown reduction {spec['what']!r}")
    table = collect(run)
    if table is None:
        return None
    want = [s.replace("/", ":") for s in spec["scopes"]]
    rx = re.compile(spec["match"])
    rows = [r for name, p in table["programs"].items() if rx.search(name)
            for r in p["whole"]]
    if not rows or not any(w in r for r in rows for w in want):
        return None
    return device_scopes._median_ms(rows, want)
