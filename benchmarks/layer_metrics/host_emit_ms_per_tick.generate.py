"""The part of ``host_ms_per_tick.generate`` the engine's thread spends
sending: tokens to the streaming replies (``engine.pump_streams``) and
finished requests answered (``engine.reply_finished``). Self time of those
spans a ``decoder.tick``, over the traced stretch (``_host_tick``)."""

from benchmarks.layer_metrics import _host_tick


def read(trace, counters, cell, config, peak):
    return _host_tick.read("emit", trace, counters)
