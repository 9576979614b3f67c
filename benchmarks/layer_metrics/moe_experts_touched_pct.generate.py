"""Distinct held experts a routed layer's decode tick touched, over the
experts held: what the grouped product has to read of the layer. From the
pool's counters over the whole window (the tick carries them out beside its
tokens)."""

from benchmarks.layer_metrics import _routed


def read(trace, counters, cell, config, peak):
    touched = _routed.moved(counters, "experts_touched")
    ticks = counters.get("kv_stats", {}).get("attn_ticks_kernel")
    chunks = counters.get("kv_stats", {}).get("prefill_chunks", 0)
    layers = _routed.routed_layers(config)
    if touched is None or not ticks or not layers:
        return None
    held = config["experts_held"][1] - config["experts_held"][0]
    return 100.0 * touched / ((ticks - chunks) * layers * held)
