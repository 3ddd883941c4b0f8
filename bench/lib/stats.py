"""The percentile the harness reads its tails with.

``percentile`` is a copy of ``repro.telemetry.requests.percentile`` (linear
interpolation, numpy's default), kept here so that a change to the program
cannot change how the benchmark reads its tails.
"""
from __future__ import annotations

from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, ``q`` in [0, 100]; None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (q / 100.0) * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    return float(vals[lo] * (1 - frac) + vals[hi] * frac)

