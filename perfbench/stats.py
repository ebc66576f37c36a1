"""Latency arithmetic, copied from the program's ``summarize_latencies``:
numpy's linear-interpolation percentile over every sample."""
from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float | None:
    """The ``q``-th percentile of ``samples``; None when there are none."""
    x = np.asarray(samples, dtype=np.float64)
    return float(np.percentile(x, q)) if x.size else None


def mean(samples) -> float | None:
    x = np.asarray(samples, dtype=np.float64)
    return float(x.mean()) if x.size else None
