"""95th percentile, over the window's streamed requests, of the mean wait
of a request's chunks in the transport: from ``StreamingReply.send`` on the
engine's thread to the return of the socket write on the connection's
(``write_lag_sum_s / writes`` on the request's timeline). What lies between
the engine's hand-over and the socket. The mean and not the request's
longest wait (``write_lag_max_s``): in every cell that one read 112-125 ms,
the longest stop of the whole process in the request's life (a full
collection of the garbage collector, the profiler's stop beside the
window), which ``gc_pause_max_ms.generate`` times at its source."""

from benchmarks.layer_metrics._common import percentile
from benchmarks.layer_metrics._timeline import window_requests


def read(trace, counters, cell, config, peak):
    waits = [a["write_lag_sum_s"] / a["writes"]
             for a in window_requests(counters) or () if a.get("writes")]
    value = percentile(waits, 0.95)
    return None if value is None else 1e3 * value
