"""95th percentile of the gaps between one request's token events, on the
client's clock, over the window."""

from benchmarks.layer_metrics._common import percentile


def read(trace, counters, cell, config, peak):
    value = percentile(counters.get("itl_gaps") or [], 0.95)
    return None if value is None else 1e3 * value
