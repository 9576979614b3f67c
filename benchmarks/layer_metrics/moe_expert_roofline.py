"""The routed experts' grouped product's share of its roofline over the traced
stretch: the bytes of the distinct held experts the traced ticks touched (the
pool's counter, scaled to the stretch) at the chip's peak bytes/s, over the
product's device seconds in the trace."""

from benchmarks import costs, costs_moe
from benchmarks.layer_metrics import _hybrid, _routed


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "moe_experts")
    touched = _routed.moved(counters, "experts_touched")
    share = _routed.traced_share(counters)
    if seconds is None or touched is None or share is None:
        return None
    nbytes = costs_moe.experts_touched_bytes(
        touched * share, config["hidden_size"],
        config["moe_intermediate_size"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
