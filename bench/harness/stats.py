"""Order statistics that refuse an empty sample."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) of ``xs``, by linear
    interpolation between closest ranks.  An empty sample has no
    percentile: it raises instead of reading as zero."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile q must be in (0, 100], got {q}")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
