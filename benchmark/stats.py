"""Arithmetic on the client's token clock: gaps, percentiles, windows."""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error, never a
    default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """Linear-interpolated q-th percentile (0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def gaps_in_window(token_times: dict, t_open: float, t_close: float) -> list:
    """token_times: request id -> ascending arrival times of its tokens.
    A gap is the time between two consecutive tokens of ONE request; it
    counts when it ENDS inside (t_open, t_close], wherever it began."""
    out = []
    for times in token_times.values():
        for a, b in zip(times, times[1:]):
            if t_open < b <= t_close:
                out.append(b - a)
    return out


def tokens_in_window(token_times: dict, t_open: float, t_close: float) -> int:
    return sum(
        1 for times in token_times.values() for t in times
        if t_open < t <= t_close
    )


def histogram(values, edges) -> list:
    """Counts per [edges[i], edges[i+1]) bin, plus one overflow bin."""
    counts = [0] * len(edges)
    for v in values:
        i = 0
        while i + 1 < len(edges) and v >= edges[i + 1]:
            i += 1
        counts[i] += 1
    return counts


def relative_errors(got, want) -> dict:
    """Root-mean-square and largest difference between the system's values
    and the reference's, as shares of the reference's standard deviation."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    std = float(want.std())
    err = got - want
    return {"reference_std": std,
            "rms_err_over_std": float(np.sqrt((err ** 2).mean())) / std,
            "max_err_over_std": float(np.abs(err).max()) / std}
