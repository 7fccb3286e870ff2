"""The percentiles the benchmark reports."""
from __future__ import annotations

import statistics
from typing import Sequence


def pct(values: Sequence[float], q: int) -> float:
    """The q-th percentile (1..99) of all `values`, inclusive method: it
    interpolates between the ranked values and never extrapolates past the
    largest."""
    if len(values) < 2:
        return float(values[0]) if values else 0.0
    return float(statistics.quantiles(values, n=100, method="inclusive")[q - 1])
