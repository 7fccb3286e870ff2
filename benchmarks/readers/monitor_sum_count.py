"""Reader `monitor_sum_count`: a mean from a monitor histogram's sum and
count over the window (never its quantiles: they come from a sketch).

args: `histogram`; `scale` (multiplies the histogram's sum, default 1);
optionally `minus` = {"histogram", "scale"}, whose window sum is subtracted
before dividing by the first histogram's count.
"""
from __future__ import annotations


def _delta(before, after, name):
    """(sum, count) a histogram gained between the two snapshots."""
    a = after["histograms"].get(name)
    if a is None:
        return None
    b = before["histograms"].get(name, {"sum": 0.0, "count": 0})
    return a["sum"] - b["sum"], a["count"] - b["count"]


def read(evidence, histogram, scale=1.0, minus=None):
    before, after = evidence.get("monitor") or (None, None)
    if before is None or after is None:
        return None
    main = _delta(before, after, histogram)
    if main is None or main[1] <= 0:
        return None
    total = main[0] * scale
    if minus is not None:
        other = _delta(before, after, minus["histogram"])
        if other is None:
            return None
        total -= other[0] * minus.get("scale", 1.0)
    return total / main[1]
