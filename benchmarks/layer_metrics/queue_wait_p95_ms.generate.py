"""95th percentile of the time a request waited in the decoder's queue for
a slot (``admitted_at - submitted_at``), over the requests submitted inside
the window. Near zero while no caller waits for a slot."""

from benchmarks.layer_metrics._common import percentile
from benchmarks.layer_metrics._timeline import spans_ms


def read(trace, counters, cell, config, peak):
    return percentile(spans_ms(counters, "submitted_at", "admitted_at")
                      or [], 0.95)
