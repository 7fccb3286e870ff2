"""Reader `monitor_counter_share`: `monitor_counter_ratio` as a percentage,
100 x (sum of `plus` - sum of `minus`) / `per`, each counter as it grew
over the window. Same args."""
from __future__ import annotations

from . import monitor_counter_ratio


def read(evidence, plus, per, minus=()):
    ratio = monitor_counter_ratio.read(evidence, plus, per, minus)
    return None if ratio is None else 100.0 * ratio
