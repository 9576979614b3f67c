"""What several per-layer readers share. A reader is
``read(trace, counters, cell, config, peak) -> number or None``: ``trace`` is
``trace_reduce.reduce_profile``'s dict, ``counters`` what the driver's window
returned plus the harness's own, ``peak`` the device's row of peaks.json."""

import math


def percentile(values, q):
    """The ``q`` quantile by nearest rank: the smallest value with at least
    ``q`` of the sample at or below it; None of an empty sample."""
    ordered = sorted(values)
    if not ordered:
        return None
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def idle_pct(trace):
    if not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
