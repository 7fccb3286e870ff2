"""Reader `monitor_counter_ratio`: (sum of `plus` counters - sum of `minus`
counters) / `per` counter, each as it grew over the window.

args: `plus` (list of counter names), `minus` (list, may be empty), `per`.
"""
from __future__ import annotations


def read(evidence, plus, per, minus=()):
    before, after = evidence.get("monitor") or (None, None)
    if before is None or after is None:
        return None

    def grew(name):
        return after["counters"].get(name, 0) - before["counters"].get(name, 0)

    steps = grew(per)
    if steps <= 0:
        return None
    return (sum(map(grew, plus)) - sum(map(grew, minus))) / steps
