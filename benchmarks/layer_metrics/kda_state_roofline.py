"""The delta-rule decode step's share of its roofline over the traced
stretch: the state bytes its calls had to move (each live slot's state read
and written once a kda layer a step: ``costs_hybrid.lightning_state_bytes``
counts exactly this) at the chip's peak bytes/s, over the step's device
seconds in the trace."""

from benchmarks import costs, costs_hybrid
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "kda_decode")
    contexts = _hybrid.traced_contexts(counters)
    if seconds is None or not contexts or "layers_held" not in config:
        return None
    layers = sum((i + 1) % config["layer_group_size"] != 0
                 for i in config["layers_held"])
    nbytes = costs_hybrid.lightning_state_bytes(
        len(contexts), layers, config["num_attention_heads"],
        config["head_dim"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
