"""Latent attention's decode kernel's share of its roofline over the traced
stretch: one ``latent + rope`` row a cached position a live row a step at the
chip's peak bytes/s, over the kernel's device seconds in the trace."""

from benchmarks import costs, costs_moe
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "latent_decode")
    contexts = _hybrid.traced_contexts(counters)
    if seconds is None or not contexts or "layers_held" not in config:
        return None
    layers = sum((i + 1) % config["layer_group_size"] == 0
                 for i in config["layers_held"])
    nbytes = costs_moe.latent_decode_bytes(
        contexts, layers, config["kv_lora_rank"],
        config["qk_rope_head_dim"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
