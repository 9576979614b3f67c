"""The prefill windows' expanded latent attention's share of its roofline
over the traced stretch: the operations the windows of the stretch needed
(K and V rebuilt from the latents of each window's context, its causal
scores and weighted values; the pool's counts OF THE STRETCH, which the
driver reads where the stretch ends) at the chip's peak FLOP/s, over the
windows' device seconds in the trace. Compute bound."""

from benchmarks import costs, costs_glm
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "latent_window")
    moved = counters.get("kv_stats_traced") or {}
    keys, pairs = (moved.get("latent_window_context"),
                   moved.get("latent_window_pairs"))
    if seconds is None or not keys or not pairs:
        return None
    flops = costs_glm.latent_window_flops(
        keys, pairs, len(config["layers_held"]),
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"])
    least, _bound = costs.roofline_seconds(flops, 0, peak)
    return 100.0 * least / seconds
