"""95th percentile of the time between the ends of successive rounds that
handed streams a token event, each interval counted once for every event
its round sent, over the traced stretch: the engine's own inter-token time,
to stand beside the client's ``itl_p95_ms.generate``."""

from benchmarks.layer_metrics._common import percentile
from benchmarks.layer_metrics._rounds import traced_rounds


def read(trace, counters, cell, config, peak):
    sent = [r for r in traced_rounds(counters) or () if r.stream_events]
    value = percentile([b.ended_at - a.ended_at
                        for a, b in zip(sent, sent[1:])
                        for _ in range(b.stream_events)], 0.95)
    return None if value is None else 1e3 * value
