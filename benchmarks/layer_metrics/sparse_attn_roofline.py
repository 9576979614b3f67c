"""The block-sparse decode attention's share of its roofline over the traced
stretch: K and V of the blocks each emitted token's query had to attend plus
the compressed keys its scorer had to scan, from the requests' positions, at
the chip's peak bytes/s, over the selected-block kernel's device seconds."""

from benchmarks import costs, costs_hybrid
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "sparse_decode")
    contexts = _hybrid.traced_contexts(counters)
    if seconds is None or not contexts:
        return None
    nbytes = costs_hybrid.sparse_decode_bytes(
        contexts, _hybrid.layers_of(config, "minicpm4"),
        config["num_key_value_heads"], config["head_dim"],
        config["sparse_config"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
