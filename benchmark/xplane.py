"""Reduction from a profiler trace to device metrics.

``load`` turns the profiler's ``.xplane.pb`` into a plain dict; everything
else works on that dict, so the arithmetic is tested on a small recorded
trace (benchmark/fixtures/) without a chip:

    {"devices": [{"ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[span name, start_ns, dur_ns], ...]}

On a TPU plane the line 'XLA Ops' holds one event per executed HLO
instruction (its name is the instruction's text, '%fusion.3 = bf16[..]
fusion(...)') and 'XLA Modules' one per executed program ('jit_f(hash)').
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s,
which land on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return hits[-1] if hits else None


def load(path: str, span_names=()) -> dict:
    from jax.profiler import ProfileData

    keep = set(span_names)
    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name
                )
                if key:
                    dev[key] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events
                    ]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name in keep
                ]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def clip(trace: dict, lo: float, hi: float) -> dict:
    """Keep what lies in [lo, hi]; events that straddle an edge are cut."""

    def cut(events):
        out = []
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append([name, a, b - a])
        return out

    return {
        "devices": [{k: cut(v) for k, v in dev.items()}
                    for dev in trace["devices"]],
        "host": cut(trace["host"]),
    }


def union(events) -> list:
    """Merged, sorted [start, end] intervals covered by any event."""
    out = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(
        sum(e - s for s, e in union(dev["ops"])) for dev in devs
    ) / len(devs) / 1e9


def short_name(hlo_text: str) -> str:
    """'%multiply_reduce_fusion.14 = bf16[32,14336]{1,0:T(8,128)} fusion(..'
    -> 'multiply_reduce_fusion bf16[32,14336]': the instruction's name
    without its serial number, and the result's type and shape."""
    m = re.match(r"%?([\w.\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])?", hlo_text)
    if not m:
        return hlo_text[:80]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:80]


def self_times(events) -> list:
    """[(name, self ns)]: an event's duration less that of the events
    nested directly inside it. A 'while' or 'conditional' instruction spans
    the instructions of its body, which would otherwise count twice."""
    out, stack = [], []  # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(d, stack[-1][0] - s)
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return [(name, max(d, 0.0)) for name, d in out]


def op_ranking(trace: dict, n: int = 10) -> list:
    """[[short name, seconds of self time on device 0], ...], longest
    first."""
    total = {}
    ops = trace["devices"][0]["ops"] if trace["devices"] else ()
    for name, d in self_times(ops):
        key = short_name(name)
        total[key] = total.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10,
              bubble_ns: float = 5000.0) -> list:
    """[[host span, seconds], ...]: every gap between device operations on
    device 0 inside [lo, hi] goes to the host span that overlaps it most
    (the innermost of equals), or to '(no span)'. Gaps under ``bubble_ns``
    are the device's own bubbles between two instructions, not the host's
    doing: they are summed under '(between ops)'."""
    if not trace["devices"]:
        return []
    edges, prev = [], lo
    for s, e in union(trace["devices"][0]["ops"]):
        if s > prev:
            edges.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        edges.append((prev, hi))
    host = sorted(trace["host"], key=lambda e: e[1])
    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0.0)
    total = {}
    for a, b in edges:
        best, best_key = "(no span)", (0.0, 0.0)
        if b - a < bubble_ns:
            best = "(between ops)"
        else:
            k = bisect.bisect_left(starts, b) - 1
            while k >= 0 and starts[k] >= a - longest:
                name, s, d = host[k]
                ov = min(b, s + d) - max(a, s)
                if ov > 0 and (ov, -d) > best_key:
                    best, best_key = name, (ov, -d)
                k -= 1
        total[best] = total.get(best, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def matching(events, pattern: str) -> list:
    """Seconds of each event whose name matches the regex."""
    rx = re.compile(pattern)
    return [d / 1e9 for name, _, d in events if rx.search(name)]


def matching_seconds(events, pattern: str) -> tuple:
    """(seconds, count) of the events whose name matches the regex."""
    hits = matching(events, pattern)
    return sum(hits), len(hits)


def collective_seconds(dev: dict) -> float:
    """Seconds device ops of a collective kind (start, done and fused
    forms) held the core: the union of their intervals."""
    rx = re.compile(r"^%?(?:" + "|".join(COLLECTIVES) + ")")
    return sum(
        e - s for s, e in union([ev for ev in dev["ops"] if rx.match(ev[0])])
    ) / 1e9
