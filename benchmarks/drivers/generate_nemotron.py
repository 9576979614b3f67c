"""Nemotron 3 Super's share behind ``GenerationEngine``'s HTTP endpoint,
closed loop: ``drivers/generate_ling.py``'s warm-up, window accounting and
check, with the repo's ``TransformerConfig`` built from the configuration
file's own keys (a mixer layer and the ``E`` layer after it are one block
layer of the program) and the state-space step's, the grouped-query
kernel's and the routed feed-forward's counters beside the scheduler's."""

import json
import threading
import time

from benchmarks import traffic
from benchmarks.drivers import generate as base
from benchmarks.drivers import generate_ling as routed
from benchmarks.references.nemotron_h import block_layers
# at import, so that a program without these mechanisms stops here, at once
# and before any weight is made
from mmlspark_tpu.models.zoo.transformer import (RoutedExperts, StateSpace,
                                                 TransformerConfig)
from mmlspark_tpu.ops.ssm_step import ssm_decode_step  # noqa: F401

MIXERS = {"attention": "gqa", "mamba": "ssm"}


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for a ``nemotron_h`` ``config.json``
    cut to a share: every number is the file's, under the program's names."""
    import jax.numpy as jnp
    kinds = block_layers(config["hybrid_override_pattern"])
    if not (len(config["hybrid_override_pattern"])
            == config["num_hidden_layers"] == len(config["layers_held"])):
        raise ValueError("layers_held, hybrid_override_pattern and "
                         "num_hidden_layers disagree")
    first, end = config["experts_held"]
    if end - first != config["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    return TransformerConfig(
        vocab=config["vocab_size"], layers=len(kinds),
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        norm_eps=float(config["layer_norm_epsilon"]), position="rope",
        qk_positions=False,
        mixers=tuple(MIXERS[m] for m, _ in kinds),
        ffn=tuple("moe" if feed else "none" for _, feed in kinds),
        ssm=StateSpace(
            heads=config["mamba_num_heads"],
            head_dim=config["mamba_head_dim"],
            state=config["ssm_state_size"], groups=config["n_groups"],
            taps=config["conv_kernel"], chunk=config["chunk_size"]),
        routed=RoutedExperts(
            experts=config["published"]["n_routed_experts"], first=first,
            count=end - first, per_token=config["num_experts_per_tok"],
            groups=config["n_group"], groups_kept=config["topk_group"],
            scale=float(config["routed_scaling_factor"]),
            d_expert=config["moe_intermediate_size"],
            d_shared=(config["moe_shared_expert_intermediate_size"]
                      * config["n_shared_experts"]),
            latent=config["moe_latent_size"],
            form=config["mlp_hidden_act"]))


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
        for label in ("ssm", "ssm_window", "gqa", "gqa_window"):
            counts["attn_ticks_" + label] = int(
                kv.get("attn_ticks_" + label, 0))
        for name in routed.MOE_COUNTS:
            counts["moe_" + name] = int(kv.get("moe_" + name, 0))
        for name in ("prefill_tokens", "ssm_state_rows"):
            counts[name] = int(kv.get(name, 0))
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
            ("pairs_computed_for_an_expert_not_held",
             max(0, m["moe_pairs_held"] - m["moe_pairs_routed"])),
            ("ticks_of_an_ssm_layer_off_ssm_decode_step",
             m["attn_ticks_ssm_window"]),
            ("ssm_ticks_missing", 0 if m["attn_ticks_ssm"] > 0 else 1),
            ("ssm_state_rows_missing", 0 if m["ssm_state_rows"] > 0 else 1),
            ("ticks_of_the_gqa_layer_off_the_grouped_query_kernel",
             m["attn_ticks_gqa_window"]),
            ("gqa_ticks_missing", 0 if m["attn_ticks_gqa"] > 0 else 1)]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
