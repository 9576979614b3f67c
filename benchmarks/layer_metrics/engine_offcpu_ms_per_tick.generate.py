"""Milliseconds a decode tick the engine's thread spent neither on the CPU
nor waiting for the device: waiting for the GIL (32 handler threads write
to their sockets meanwhile), for a lock, in a blocking call. The round
log's ``wall_s - cpu_s - wait_s`` summed over the traced stretch's rounds,
over their ticks. A span's wall time cannot tell this from work."""

from benchmarks.layer_metrics._rounds import traced_rounds


def read(trace, counters, cell, config, peak):
    rows = traced_rounds(counters)
    ticks = sum(r.ticks for r in rows or ())
    if not ticks:
        return None
    return 1e3 * sum(r.wall_s - r.cpu_s - r.wait_s for r in rows) / ticks
