"""The part of ``host_ms_per_tick.generate`` the engine's thread spends
handing the device its work: the tick's dispatch, prefills and chunk
windows, staged prefills, state snapshots and restores, the pool's
defragmentation. Self time of those spans a ``decoder.tick``, over the
traced stretch (``_host_tick``)."""

from benchmarks.layer_metrics import _host_tick


def read(trace, counters, cell, config, peak):
    return _host_tick.read("launch", trace, counters)
