"""The linear-attention decode step's share of its roofline over the traced
stretch: the state bytes its calls had to move (each live slot's state read
and written once a lightning layer a step) at the chip's peak bytes/s, over
the step's device seconds in the trace."""

from benchmarks import costs, costs_hybrid
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "lightning_decode")
    contexts = _hybrid.traced_contexts(counters)
    if seconds is None or not contexts:
        return None
    nbytes = costs_hybrid.lightning_state_bytes(
        len(contexts), _hybrid.layers_of(config, "lightning-attn"),
        config["lightning_nh"], config["lightning_head_dim"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
