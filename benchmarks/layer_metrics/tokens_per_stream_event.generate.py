"""Tokens a token event carries as the engine hands it to a streaming
reply, over the traced stretch's rounds. 1.0: the engine never gives a
client two tokens at once, and a gap of two steps on the client's clock
(``itl_p95_ms.generate`` times events) is made after it; more: a round
pumped two drained blocks as one event."""

from benchmarks.layer_metrics._rounds import traced_rounds


def read(trace, counters, cell, config, peak):
    rows = traced_rounds(counters)
    events = sum(r.stream_events for r in rows or ())
    if not events:
        return None
    return sum(r.stream_tokens for r in rows) / events
