"""LFM2-24B-A2B's stage-share behind ``GenerationEngine``'s HTTP endpoint,
closed loop: ``drivers/generate_ling.py``'s warm-up, window accounting and
routed comparisons, with the repo's ``TransformerConfig`` built from the
configuration file's own keys and the two mixers' counters beside the
routed feed-forward's and the scheduler's."""

import json
import threading
import time

from benchmarks import traffic
from benchmarks.drivers import generate as base
from benchmarks.drivers import generate_ling as routed
# at import, so that a program without these mechanisms stops here, at once
# and before any weight is made
from mmlspark_tpu.models.zoo.transformer import (RoutedExperts, ShortConv,
                                                 TransformerConfig)
from mmlspark_tpu.ops.paged_attention import paged_attention_gqa  # noqa: F401

MIXERS = {"conv": "conv", "full_attention": "gqa"}


def layer_kinds(config):
    """``[(mixer, feed-forward)]`` of the layers held: ``layer_types`` under
    the program's names, the first ``num_dense_layers`` dense."""
    return [(MIXERS[kind],
             "dense" if j < config["num_dense_layers"] else "moe")
            for j, kind in enumerate(config["layer_types"])]


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for an ``lfm2_moe`` ``config.json``
    cut in depth: every number is the file's, under the program's names."""
    import jax.numpy as jnp
    kinds = layer_kinds(config)
    if not (len(kinds) == config["num_hidden_layers"]
            == len(config["layers_held"])):
        raise ValueError("layers_held, layer_types and num_hidden_layers "
                         "disagree")
    if config["experts_held"] != [0, config["num_experts"]]:
        raise ValueError("every expert is held: experts_held and "
                         "num_experts disagree")
    return TransformerConfig(
        vocab=config["vocab_size"], layers=len(kinds),
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=(config.get("head_dim") or config["hidden_size"]
                  // config["num_attention_heads"]),
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        norm_eps=float(config["norm_eps"]), position="rope",
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        mixers=tuple(m for m, _ in kinds), ffn=tuple(f for _, f in kinds),
        conv=ShortConv(taps=config["conv_L_cache"]),
        routed=RoutedExperts(
            experts=config["num_experts"], first=0, count=0,
            per_token=config["num_experts_per_tok"], groups=1, groups_kept=1,
            scale=float(config["routed_scaling_factor"]),
            d_expert=config["moe_intermediate_size"], d_shared=0))


class Driver(routed.Driver):
    def __init__(self, cell, config, seed, reference):
        # generate_ling's, but for the mapping (it reads its own module's)
        from mmlspark_tpu.serving.generation import GenerationEngine
        self.cell, self.config, self.seed, self.ref = (
            cell, config, seed, reference)
        self.mix = traffic.load(cell["traffic"])
        t0 = time.perf_counter()
        self.params = reference.make_weights(config, seed)
        self.plan = traffic.closed_loop_requests(self.mix, seed,
                                                 config["vocab_size"])
        t1 = time.perf_counter()
        self.engine = GenerationEngine(
            self.params, program_config(config, cell["max_len"]),
            max_slots=cell["slots"], max_len=cell["max_len"],
            reply_timeout=600.0, **cell.get("engine", {}))
        self.where = dict(weights_s=t1 - t0,
                          engine_s=time.perf_counter() - t1)
        self.closing = threading.Event()
        self.clients = []
        self.records = []

    def counters(self):
        kv = self.engine.decoder._kv.stats
        counts = dict(base.Driver.counters(self))
        for label in ("conv", "gqa", "gqa_window"):
            counts["attn_ticks_" + label] = int(
                kv.get("attn_ticks_" + label, 0))
        for name in routed.MOE_COUNTS:
            counts["moe_" + name] = int(kv.get("moe_" + name, 0))
        counts["prefill_tokens"] = int(kv.get("prefill_tokens", 0))
        return counts

    def check(self):
        t0 = time.perf_counter()
        compared = base.Driver.check(self)
        print(json.dumps(dict(reference_s=time.perf_counter() - t0)),
              flush=True)
        m = self.moved
        exact = [
            ("routed_pairs_dropped", m["moe_pairs_dropped"]),
            ("routed_pairs_misplaced", m["moe_pairs_misplaced"]),
            ("routed_pairs_missing", 0 if m["moe_pairs_held"] > 0 else 1),
            ("routed_pairs_not_on_a_held_expert",
             abs(m["moe_pairs_routed"] - m["moe_pairs_held"])),
            ("ticks_of_a_gqa_layer_off_the_grouped_query_kernel",
             m["attn_ticks_gqa_window"]),
            ("gqa_ticks_missing", 0 if m["attn_ticks_gqa"] > 0 else 1),
            ("conv_ticks_missing", 0 if m["attn_ticks_conv"] > 0 else 1)]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
