"""Percentiles as ``repro.serving.metrics.percentile`` takes them
(nearest rank, no interpolation: a p95 is a latency some request saw)."""
from __future__ import annotations

import math
from typing import List, Optional


def percentile(samples: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on no samples."""
    if not samples:
        return None
    xs = sorted(samples)
    rank = max(1, math.ceil(len(xs) * q / 100))
    return float(xs[min(rank, len(xs)) - 1])
