"""Percentile and rate arithmetic of the end-to-end metrics, kept with the
benchmark so that no change to the program can move it."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated ``q``-th percentile (numpy's default rule); a
    metric of no samples is an error, not 0."""
    if len(xs) == 0:
        raise ValueError(f"no samples for the {q}th percentile")
    return float(np.percentile(np.asarray(xs, np.float64), q))


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return count / seconds
