"""Percentiles, as the program's ``repro.loadgen.stats.percentile`` takes
them (nearest rank: always an observed value), kept here so that no
later change to the program can change a reported tail."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile (0 <= q <= 100); raises on an empty
    sample rather than invent a latency."""
    if not 0 <= q <= 100:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    s = sorted(float(x) for x in xs)
    if not s:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(rank, len(s)) - 1]
