"""Ling-3.0-flash's share behind ``GenerationEngine``'s HTTP endpoint, closed
loop: ``drivers/generate.py``'s callers, window accounting and check, with the
repo's ``TransformerConfig`` built from the configuration file's own keys,
the chunked prefill of every prompt warmed a chunk width at a time (a hybrid
decoder has no batched prefill), and the routed feed-forward's and the two
mixers' counters beside the scheduler's."""

import json
import time

import numpy as np

from benchmarks.drivers import generate as base
# at import, so that a program without these mechanisms stops here, at once
# and before any weight is made
from mmlspark_tpu.models.zoo.transformer import (DeltaRule, LatentAttention,
                                                 RoutedExperts,
                                                 TransformerConfig)

MOE_COUNTS = ("pairs_routed", "pairs_held", "pairs_dropped",
              "pairs_misplaced", "experts_touched", "expert_load_max")


def layer_kinds(config):
    """``[(mixer, feed-forward)]`` of the layers held, from their PUBLISHED
    indices: latent attention closes each group of ``layer_group_size``."""
    return [("mla" if (i + 1) % config["layer_group_size"] == 0 else "kda",
             "dense" if j < config["first_k_dense_replace"] else "moe")
            for j, i in enumerate(config["layers_held"])]


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for a Ling-3.0-flash ``config.json``
    cut to a share: every number is the file's, under the program's names."""
    import jax.numpy as jnp
    kinds = layer_kinds(config)
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    first, end = config["experts_held"]
    if end - first != config["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    routed = [i for (_, feed), i in zip(kinds, config["layers_held"])
              if feed == "moe"]
    return TransformerConfig(
        vocab=config["vocab_size"], layers=len(kinds),
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        position="rope", rope_theta=float(config["rope_theta"]),
        mixers=tuple(m for m, _ in kinds), head_dim=config["head_dim"],
        ffn=tuple(f for _, f in kinds),
        routed=RoutedExperts(
            experts=config["published"]["num_experts"], first=first,
            count=end - first, per_token=config["num_experts_per_tok"],
            groups=config["n_group"], groups_kept=config["topk_group"],
            scale=float(config["routed_scaling_factor"]),
            d_expert=config["moe_intermediate_size"],
            d_shared=config["moe_shared_expert_intermediate_size"],
            swiglu_limits=tuple(
                config[key][i] for i in routed
                for key in ("expert_swiglu_limit_list",
                            "share_expert_swiglu_limit_list"))),
        latent=LatentAttention(
            latent=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
            rope=config["qk_rope_head_dim"], value=config["v_head_dim"]),
        kda=DeltaRule(conv_kernel=config["short_conv_kernel_size"],
                      gate_floor=float(config["kda_lower_bound"])))


class Driver(base.Driver):
    def __init__(self, cell, config, seed, reference):
        import threading

        from benchmarks import traffic
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
        for label in ("kda", "kda_window", "latent", "latent_window"):
            counts["attn_ticks_" + label] = int(
                kv.get("attn_ticks_" + label, 0))
        for name in MOE_COUNTS:
            counts["moe_" + name] = int(kv.get("moe_" + name, 0))
        return counts

    def warm(self):
        """Every program the traffic can reach, run once on the decoder: the
        chunk window at each width a prompt's last chunk can pad to (all of
        a hybrid decoder's prompts prefill in chunks), the tick, the pool's
        defragmentation. Then the engine's thread and the closed loop for
        ``ramp_seconds``."""
        rng = np.random.default_rng(self.seed + 2)
        w = self.mix["warm"]
        t0 = time.perf_counter()
        for n in w["chunked_prompts"]:
            self.drive(rng, [(n, 2)])
        moves = self.counters()["defrag_moves"]
        self.drive(rng, list(zip(w["defrag"]["prompts"],
                                 w["defrag"]["outputs"])))
        if self.counters()["defrag_moves"] == moves:
            raise RuntimeError("warm-up did not reach the pool's "
                               "defragmentation: the traffic file's "
                               "warm.defrag no longer provokes it")
        self.where.update(warm_programs_s=time.perf_counter() - t0)
        print(json.dumps(dict(setup_where=self.where)), flush=True)
        self.engine.start()
        self.clients = [
            base.Client(self.engine.address, plan, self.closing,
                        i * self.mix["start_stagger_s"])
            for i, plan in enumerate(self.plan)]
        for c in self.clients:
            c.start()
        time.sleep(self.mix["ramp_seconds"])

    def check(self):
        t0 = time.perf_counter()
        compared = base.Driver.check(self)
        print(json.dumps(dict(reference_s=time.perf_counter() - t0)),
              flush=True)
        m = self.moved
        exact = [
            ("routed_pairs_dropped", m["moe_pairs_dropped"]),
            ("routed_pairs_missing", 0 if m["moe_pairs_held"] > 0 else 1),
            ("ticks_of_a_kda_layer_off_kda_decode_step",
             m["attn_ticks_kda_window"]),
            ("kda_ticks_missing", 0 if m["attn_ticks_kda"] > 0 else 1),
            ("ticks_of_the_mla_layer_off_the_absorbed_kernel",
             m["attn_ticks_latent_window"]),
            ("latent_ticks_missing", 0 if m["attn_ticks_latent"] > 0 else 1),
            ("pairs_computed_for_an_expert_not_held",
             m["moe_pairs_misplaced"]
             + max(0, m["moe_pairs_held"] - m["moe_pairs_routed"]))]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
