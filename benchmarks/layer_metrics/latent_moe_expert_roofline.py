"""The grouped product's share of its roofline where the experts live in a
latent: the bytes of the distinct held experts the traced ticks touched (the
pool's counter, scaled to the stretch), an expert ``2 x moe_latent_size x
moe_intermediate_size`` values, at the chip's peak bytes/s, over the
product's device seconds in the trace. None where the configuration names no
latent."""

from benchmarks import costs, costs_nemotron
from benchmarks.layer_metrics import _hybrid, _routed


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "moe_experts")
    touched = _routed.moved(counters, "experts_touched")
    share = _routed.traced_share(counters)
    if (seconds is None or touched is None or share is None
            or not config.get("moe_latent_size")):
        return None
    nbytes = costs_nemotron.latent_experts_touched_bytes(
        touched * share, config["moe_latent_size"],
        config["moe_intermediate_size"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
