"""The part of ``host_ms_per_tick.generate`` the engine's thread spends
deciding what runs: reading the HTTP queue (``engine.admit_http``),
admission (``decoder.admit``) and ``decoder.step``'s own time (locks, the
live lists, the chunk scheduler's bookkeeping). Self time of those spans a
``decoder.tick``, over the traced stretch (``_host_tick``)."""

from benchmarks.layer_metrics import _host_tick


def read(trace, counters, cell, config, peak):
    return _host_tick.read("schedule", trace, counters)
