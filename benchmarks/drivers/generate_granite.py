"""Short questions about a few long stored contexts behind
``GenerationEngine``'s HTTP endpoint, closed loop, on Granite 4.0-H-Small's
share: ``drivers/generate_glm.py``'s callers, stored contexts, warm-up and
traced reading (several callers a context, so live rows hold the same stored
pages), with the repo's ``TransformerConfig`` built from the configuration
file's own keys and ``drivers/generate_nemotron.py``'s exact checks. Nine
layers in ten hold a state and a convolution's tail, one holds pages: a
stored context is pages AND a snapshot of those rows, and every admission of
the window shares the first and restores the second."""

import json
import threading
import time

import numpy as np

from benchmarks import traffic
from benchmarks.drivers import generate as base
from benchmarks.drivers import generate_docs as docs
from benchmarks.drivers import generate_glm as shared
from benchmarks.drivers import generate_ling as routed
from benchmarks.references.granitemoehybrid import head_dim, layer_kinds
# at import, so that a program without these mechanisms (the step that
# splits one group of 128 heads came with them) stops here, at once and
# before any weight is made
from mmlspark_tpu.models.zoo.transformer import (RoutedExperts, StateSpace,
                                                 TransformerConfig)
from mmlspark_tpu.ops.ssm_step import pairs_a_step  # noqa: F401

MIXERS = {"attention": "gqa", "mamba": "ssm"}

#: the pool's counts the window's deltas hold beside the scheduler's
POOL_COUNTS = ("attn_ticks_ssm", "attn_ticks_ssm_window", "attn_ticks_gqa",
               "attn_ticks_gqa_window", "ssm_state_rows", "prefill_tokens",
               "prefill_chunks_riding", "prefix_misses",
               "prefix_tokens_shared", "state_snapshots_restored",
               "state_snapshot_bytes_restored")


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for a ``granitemoehybrid``
    ``config.json`` cut to a share: every number is the file's, under the
    program's names."""
    import jax.numpy as jnp
    kinds = layer_kinds(config)
    if not len(kinds) == config["num_hidden_layers"] == len(
            config["layers_held"]):
        raise ValueError("layers_held, layer_types and num_hidden_layers "
                         "disagree")
    first, end = config["experts_held"]
    if end - first != config["num_local_experts"]:
        raise ValueError("experts_held and num_local_experts disagree")
    if (config["position_embedding_type"] != "nope"
            or config["normalization_function"] != "rmsnorm"
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
            or config["mamba_expand"] * config["hidden_size"]
            != config["mamba_n_heads"] * config["mamba_d_head"]):
        raise ValueError("only the published form is built: no positions, "
                         "RMSNorm, SiLU, no biases but the convolution's, "
                         "mamba_expand x hidden_size channels")
    return TransformerConfig(
        vocab=config["vocab_size"], layers=len(kinds),
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=head_dim(config),
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]), position="rope",
        qk_positions=False,
        attn_scale=float(config["attention_multiplier"]),
        tied_head=bool(config["tie_word_embeddings"]),
        embed_scale=float(config["embedding_multiplier"]),
        residual_scale=float(config["residual_multiplier"]),
        logit_scale=1.0 / config["logits_scaling"],
        mixers=tuple(MIXERS[m] for m in kinds), ffn=("moe",) * len(kinds),
        ssm=StateSpace(
            heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
            state=config["mamba_d_state"], groups=config["mamba_n_groups"],
            taps=config["mamba_d_conv"], chunk=config["mamba_chunk_size"]),
        routed=RoutedExperts(
            experts=config["published"]["num_local_experts"], first=first,
            count=end - first, per_token=config["num_experts_per_tok"],
            d_expert=config["intermediate_size"],
            d_shared=config["shared_intermediate_size"], score="softmax"))


class Driver(shared.Driver):
    def __init__(self, cell, config, seed, reference):
        # generate_glm's, but for the mapping (it reads its own module's)
        from mmlspark_tpu.serving.generation import GenerationEngine
        self.cell, self.config, self.seed, self.ref = (
            cell, config, seed, reference)
        self.mix = traffic.load(cell["traffic"])
        count = self.mix["documents"]["count"]
        if self.mix["clients"] % count:
            raise ValueError("the callers share the contexts evenly")
        t0 = time.perf_counter()
        self.params = reference.make_weights(config, seed)
        vocab = config["vocab_size"]
        self.docs = docs.documents(self.mix, seed, vocab)
        self.plan = [
            [(key, len(doc), np.concatenate([doc, question]), want)
             for question, want in cycle]
            for j, cycle in enumerate(traffic.closed_loop_requests(
                self.mix, seed, vocab))
            for key, doc in [self.docs[j % count]]]
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
        """Under the lock a step holds, as ``generate_glm``'s (an admission
        counts its shared pages, its hit and its restored snapshot at
        moments a reading from this thread could part)."""
        decoder = self.engine.decoder
        with decoder._engine_lock:
            kv, stats = decoder._kv.stats, decoder.stats
            counts = dict(base.Driver.counters(self))
            for name in POOL_COUNTS:
                counts[name] = int(kv.get(name, 0))
            for name in routed.MOE_COUNTS:
                counts["moe_" + name] = int(kv.get("moe_" + name, 0))
            for name in ("prefix_hits", "prefix_hit_tokens"):
                counts[name] = int(stats.get(name, 0))
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
            ("gqa_ticks_missing", 0 if m["attn_ticks_gqa"] > 0 else 1),
            ("prefix_misses_in_window", m["prefix_misses"]),
            ("shared_tokens_short_of_the_contexts",
             abs(m["prefix_hit_tokens"] - m["prefix_tokens_shared"])),
            ("admissions_without_a_restored_snapshot",
             abs(m["prefix_hits"] - m["state_snapshots_restored"])),
            ("prefix_hits_missing", 0 if m["prefix_hits"] > 0 else 1)]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
