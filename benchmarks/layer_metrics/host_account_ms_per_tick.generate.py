"""The part of ``host_ms_per_tick.generate`` the engine's thread spends
counting what it dispatched and drained (``decoder.account``): the cost
ledger's shares, the pool's tick and sweep counters. Self time of that span
a ``decoder.tick``, over the traced stretch (``_host_tick``)."""

from benchmarks.layer_metrics import _host_tick


def read(trace, counters, cell, config, peak):
    return _host_tick.read("account", trace, counters)
