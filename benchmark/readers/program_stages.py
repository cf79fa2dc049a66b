"""Per-layer metrics read from the program's own stages
(``progen_tpu.telemetry.spans.stage``): from the in-memory ring, whose
clock is the window's (``perf_counter``), and from the profiler's trace,
where the same stages stand as host events on the device's clock.

The spec's ``what`` picks the reduction:

  ``per_call``    ring: self time of the stages in ``num`` over the calls
                  of the stage ``den``, in ms
  ``calls``       ring: ``stat`` (mean or median) over the ``stage``
                  calls of their duration, in ms; of their self time
                  where ``self`` is true; only of the calls that have a
                  direct child named ``child`` where one is given
  ``idle``        trace: device-0 idle seconds attributed to the stages in
                  ``stages`` over the host events of ``den``, in ms

Idle attribution: every gap between device operations is split among the
INNERMOST stage open at each instant of it, so the parts add up to the
gap; what no stage covers is '(no span)' and gaps shorter than a device
bubble are '(between ops)', as ``xplane.idle_gaps`` names them.

The first call also writes ``<out_dir>/program_stages.json``: per stage
its calls, total and self seconds in the window, and its idle seconds in
the traced part, with the two remainders. A program without stages (or
without this ring) gives every metric nothing to read: ``None``.
"""

from __future__ import annotations

import json
import re
import statistics

from benchmark import xplane

BUBBLE_NS = 5000.0  # xplane.idle_gaps' own threshold


def child_seconds(records) -> dict:
    """{seq: seconds its direct children took}. A ring tuple starts
    ``(seq, parent_seq, name, t0, dur, ...)``."""
    child_s = {}
    for r in records:
        if r[1] is not None:
            child_s[r[1]] = child_s.get(r[1], 0.0) + r[4]
    return child_s


def ring_table(records) -> dict:
    """{name: {"calls", "total_s", "self_s"}} from the ring's tuples:
    self time is the duration less that of the direct children."""
    child_s = child_seconds(records)
    table = {}
    for r in records:
        row = table.setdefault(r[2], {"calls": 0, "total_s": 0.0, "self_s": 0.0})
        row["calls"] += 1
        row["total_s"] += r[4]
        row["self_s"] += max(r[4] - child_s.get(r[0], 0.0), 0.0)
    return table


def call_seconds(records, stage: str, child=None, own: bool = False) -> list:
    """Seconds of each ``stage`` call: its duration, or its self time
    where ``own``; only the calls with a direct child named ``child``
    where one is given."""
    child_s = child_seconds(records) if own else {}
    parents = {r[1] for r in records if r[2] == child and r[1] is not None}
    return [max(r[4] - child_s.get(r[0], 0.0), 0.0) for r in records
            if r[2] == stage and (child is None or r[0] in parents)]


def innermost_segments(host) -> list:
    """[(start, end, name)] covering every instant at which a host event
    is open, named by the innermost one (the latest started)."""
    points = []
    for i, (name, s, d) in enumerate(host):
        if d > 0:
            points.append((s, 1, i))
            points.append((s + d, 0, i))
    points.sort()  # at one instant, ends before starts
    open_, out, prev = [], [], None
    for t, is_start, i in points:
        if open_ and t > prev:
            out.append((prev, t, host[open_[-1]][0]))
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        prev = t
    return out


def idle_by_stage(trace: dict, lo: float, hi: float) -> dict:
    """{stage name | '(no span)' | '(between ops)': idle seconds} for
    device 0 within [lo, hi]."""
    if not trace["devices"]:
        return {}
    gaps, prev = [], lo
    for s, e in xplane.union(trace["devices"][0]["ops"]):
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    segments = innermost_segments(trace["host"])
    total, k = {}, 0
    for a, b in gaps:
        if b - a < BUBBLE_NS:
            total["(between ops)"] = total.get("(between ops)", 0.0) + (b - a)
            continue
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        covered, j = 0.0, k
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                total[name] = total.get(name, 0.0) + ov
                covered += ov
            j += 1
        if b - a > covered:
            total["(no span)"] = total.get("(no span)", 0.0) + (b - a - covered)
    return {name: ns / 1e9 for name, ns in total.items()}


def overheads(trace: dict, stage: str, program: str) -> dict:
    """Each host event named ``stage`` with the device-0 execution of
    ``program`` (a regex) that overlaps it most: ``seconds`` is the host
    event's duration less the execution's, per pair. No metric reads it
    since the served loop launches step N+1 before it fetches step N (a
    stage then ends with an execution launched a call earlier, and the
    difference reads negative): it stays for reading a trace by hand and
    for the bound below. Durations only, so
    it holds however far the device's clock is off the host's;
    ``clock_bounds_ms`` says how far that can be: the execution cannot
    start before the stage that launches it nor end after the stage
    that fetches its result, so the device's clock runs behind the
    host's by at least the first and at most the second number."""
    if not trace["devices"]:
        return {"seconds": [], "clock_bounds_ms": None}
    rx = re.compile(program)
    runs = [(s, s + d) for name, s, d in trace["devices"][0]["modules"]
            if rx.search(name)]
    seconds, behind_min, behind_max = [], None, None
    for name, s, d in trace["host"]:
        if name != stage:
            continue
        best = max(runs, key=lambda r: min(r[1], s + d) - max(r[0], s),
                   default=None)
        if best is None or min(best[1], s + d) <= max(best[0], s):
            continue
        seconds.append((d - (best[1] - best[0])) / 1e9)
        lo, hi = (s - best[0]) / 1e6, (s + d - best[1]) / 1e6
        behind_min = lo if behind_min is None else max(behind_min, lo)
        behind_max = hi if behind_max is None else min(behind_max, hi)
    return {"seconds": seconds,
            "clock_bounds_ms": None if not seconds else [behind_min, behind_max]}


def _program_trace(run, names):
    """The traced part of the window with the PROGRAM's stages as host
    events: the harness kept only its own span names when it loaded the
    trace, so the file is read once more, here."""
    trace_dir = getattr(run, "_trace_dir", None)
    if run.trace_window is None or trace_dir is None or not names:
        return None
    path = xplane.find_xplane(str(trace_dir))
    if path is None:
        return None
    lo, hi = run.trace_window
    return xplane.clip(xplane.load(path, names), lo, hi)


def collect(run) -> dict:
    """What every metric of this module reads, gathered once a run and
    kept on it: the ring's records of the window, their table, the traced
    part with the program's stages, and its idle attribution."""
    data = getattr(run, "_program_stages", None)
    if data is not None:
        return data
    data = {"records": [], "table": {}, "trace": None, "idle": {}}
    try:
        from progen_tpu.telemetry.spans import get_telemetry

        read = get_telemetry().stages
    except (ImportError, AttributeError):
        read = None  # a program from before the stages
    if read is not None and run.t_open is not None:
        data["records"] = read(since=run.t_open, until=run.t_close)
        data["table"] = ring_table(data["records"])
        data["trace"] = _program_trace(run, set(data["table"]))
        if data["trace"] is not None:
            data["idle"] = idle_by_stage(data["trace"], *run.trace_window)
    run._program_stages = data
    write_table(run, data)
    return data


def write_table(run, data) -> None:
    if not data["table"]:
        return
    traced_calls = {}
    for name, _, _ in (data["trace"] or {"host": []})["host"]:
        traced_calls[name] = traced_calls.get(name, 0) + 1
    stages = {
        name: {**row, "traced_calls": traced_calls.get(name, 0),
               "idle_s": data["idle"].get(name, 0.0)}
        for name, row in sorted(data["table"].items())
    }
    out = {
        "cell": run.cell["name"], "seed": run.seed,
        "window_s": run.t_close - run.t_open,
        "traced_window_s": (None if run.trace_window is None else
                            (run.trace_window[1] - run.trace_window[0]) / 1e9),
        "stages": stages,
        "idle_no_span_s": data["idle"].get("(no span)", 0.0),
        "idle_between_ops_s": data["idle"].get("(between ops)", 0.0),
        "idle_total_s": sum(data["idle"].values()),
    }
    (run.out_dir / "program_stages.json").write_text(json.dumps(out, indent=1))


def read(run, spec):
    what = spec["what"]
    if what not in ("per_call", "calls", "idle"):
        raise ValueError(f"program_stages: unknown reduction {what!r}")
    data = collect(run)
    table = data["table"]
    if what == "per_call":
        calls = table.get(spec["den"], {}).get("calls")
        if not calls or not any(n in table for n in spec["num"]):
            return None
        own = sum(table[n]["self_s"] for n in spec["num"] if n in table)
        return 1000.0 * own / calls
    if what == "calls":
        secs = call_seconds(data["records"], spec["stage"], spec.get("child"),
                            bool(spec.get("self")))
        if not secs:
            return None
        stat = statistics.median if spec["stat"] == "median" else statistics.fmean
        return 1000.0 * stat(secs)
    if data["trace"] is None or not data["trace"]["devices"]:
        return None
    calls = sum(1 for e in data["trace"]["host"] if e[0] == spec["den"])
    if not calls or not any(n in table for n in spec["stages"]):
        return None
    return 1000.0 * sum(data["idle"].get(n, 0.0) for n in spec["stages"]) / calls
