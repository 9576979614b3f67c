"""95th percentile, on the engine's clock, of the time from a request's
submission to the decoder to its first token on the host, over the requests
submitted inside the window."""

from benchmarks.layer_metrics._common import percentile
from benchmarks.layer_metrics._timeline import spans_ms


def read(trace, counters, cell, config, peak):
    return percentile(spans_ms(counters, "submitted_at", "first_token_at")
                      or [], 0.95)
