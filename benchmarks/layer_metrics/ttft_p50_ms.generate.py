"""Median, on the client's clock, of the time from sending the POST to the
first streamed token, over the requests sent inside the window."""

from benchmarks.layer_metrics._common import percentile


def read(trace, counters, cell, config, peak):
    value = percentile(counters.get("ttft") or [], 0.5)
    return None if value is None else 1e3 * value
