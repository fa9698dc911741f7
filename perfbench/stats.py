"""Percentiles. Imports nothing but the standard library."""
from __future__ import annotations

from typing import Optional, Sequence

#: A percentile is reported only with this many samples beyond it.
SAMPLES_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, q in [0, 1] (copied from
    benchmarks/serve_bench.py `pct_ms`, without the rounding and the
    unit): nearest-rank collapses distinct percentiles onto one sample
    at benchmark-sized N."""
    if not values:
        return None
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(n * (1.0 - q) + 1e-9)


def tail_is_supported(n: int, q: float) -> bool:
    """The ten-samples-beyond rule: p95 wants 200 samples."""
    return samples_beyond(n, q) >= SAMPLES_BEYOND
