"""The arithmetic of the end-to-end metrics, kept with the benchmark."""

from __future__ import annotations

import math
from typing import Sequence

MIN_INTERVALS = 100  # a 90th percentile needs ten samples beyond it


class TooFewSamples(ValueError):
    pass


def percentile(values: Sequence[float], q: float,
               min_samples: int = MIN_INTERVALS) -> float:
    """Nearest-rank percentile of ALL the values (nothing trimmed): the
    smallest value with at least q percent of the samples at or below it.
    Refuses to answer over fewer than `min_samples`."""
    n = len(values)
    if n < min_samples:
        raise TooFewSamples(
            f"{n} samples, need {min_samples} for the {q}th percentile")
    if not 0 < q <= 100:
        raise ValueError(q)
    return sorted(values)[max(0, math.ceil(q / 100.0 * n) - 1)]
