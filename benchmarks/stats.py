"""Arithmetic shared by the drivers and the layer-metric readers: percentiles,
token gaps, and the table of peaks.  Plain Python: importing it touches no JAX."""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as numpy's default does; an empty input is an error, because
    a tail of nothing is not a number."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def token_gaps_ms(frames: Sequence[Sequence[float]]) -> List[float]:
    """Per-token gaps of one streamed request.  ``frames`` is its list of
    ``(arrival_seconds, tokens_in_frame)``.  Every frame after the first
    gives one sample per token: (arrival - previous arrival) / tokens."""
    out: List[float] = []
    for (t_prev, _), (t, n) in zip(frames, frames[1:]):
        if n > 0:
            out.extend([(t - t_prev) * 1e3 / n] * int(n))
    return out


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` as the contract says."""
    import statistics

    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else float("inf")


def load_peaks(device_kind: str, path: Optional[str] = None) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(known: {sorted(table)}): add its published peaks with their source"
        )
    return table[device_kind]
