"""The grouped-query paged decode kernel's share of its roofline over the
traced stretch: K and V of every cached position of every row that got a
token, once a KV head a ``full_attention`` layer, at the chip's peak bytes/s,
over the kernel's device seconds in the trace. Counted from the requests'
positions, whatever the page."""

from benchmarks import costs, costs_lfm2
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "gqa_decode")
    contexts = _hybrid.traced_contexts(counters)
    layers = sum(kind == "full_attention"
                 for kind in config.get("layer_types", ()))
    if seconds is None or not contexts or not layers:
        return None
    nbytes = costs_lfm2.gqa_decode_bytes(
        contexts, layers, config["num_key_value_heads"],
        config.get("head_dim") or config["hidden_size"]
        // config["num_attention_heads"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
