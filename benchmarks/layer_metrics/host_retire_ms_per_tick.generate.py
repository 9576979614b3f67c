"""The part of ``host_ms_per_tick.generate`` the engine's thread spends
taking a drained block apart (``decoder.retire``): the routed counts, its
tokens to their requests, the journal, finished slots released. Self time of
that span a ``decoder.tick``, over the traced stretch (``_host_tick``)."""

from benchmarks.layer_metrics import _host_tick


def read(trace, counters, cell, config, peak):
    return _host_tick.read("retire", trace, counters)
