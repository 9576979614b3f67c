"""Short questions about a few long shared contexts behind ``GenerationEngine``'s
HTTP endpoint, closed loop, on GLM-4.7-Flash's stage-share:
``drivers/generate_docs.py``'s callers (every request ``context + question``
under the context's ``prefix_key`` / ``prefix_len``) with SEVERAL callers a
context, so that live rows hold the same stored pages, and
``drivers/generate_ling.py``'s routed comparisons. Every layer's cache is
latent pages: a stored context is pages alone.

The traffic file's ``documents`` fixes the contexts' lengths; the seed
decides which key holds which length, the tokens, and (through
``traffic.closed_loop_requests``, whose prompts are the questions here) each
caller's questions; caller ``j`` asks about context ``j % count``. Every seed
carries the same work.
"""

import json
import threading
import time

import numpy as np

from benchmarks import traffic
from benchmarks.drivers import generate as base
from benchmarks.drivers import generate_docs as docs
from benchmarks.drivers import generate_ling as routed
# at import, so that a program without these mechanisms stops here, at once
# and before any weight is made
from mmlspark_tpu.models.zoo.hybrid import window_tile  # noqa: F401
from mmlspark_tpu.models.zoo.transformer import (LatentAttention,
                                                 RoutedExperts,
                                                 TransformerConfig)

#: the pool's counts the window's deltas hold beside the scheduler's
POOL_COUNTS = ("attn_ticks_latent", "attn_ticks_latent_window",
               "prefill_tokens", "prefill_chunks_riding", "prefix_misses",
               "prefix_tokens_shared", "latent_window_keys",
               "latent_window_context", "latent_window_pairs")


def layer_kinds(config):
    """The feed-forward of each layer held; every mixer is ``mla``."""
    return ["dense" if j < config["first_k_dense_replace"] else "moe"
            for j in range(len(config["layers_held"]))]


def program_config(config, max_len):
    """The repo's ``TransformerConfig`` for a ``glm4_moe_lite``
    ``config.json`` cut in depth: every number is the file's, under the
    program's names."""
    import jax.numpy as jnp
    feeds = layer_kinds(config)
    if len(feeds) != config["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    if config["experts_held"] != [0, config["n_routed_experts"]]:
        raise ValueError("every expert is held: experts_held and "
                         "n_routed_experts disagree")
    if config["rope_scaling"] is not None or \
            config["partial_rotary_factor"] != 1:
        raise ValueError("rope_scaling / partial_rotary_factor: only the "
                         "published null / 1 is built")
    return TransformerConfig(
        vocab=config["vocab_size"], layers=len(feeds),
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_len=max_len, causal=True,
        dtype=jnp.dtype(config["compute_dtype"]), norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]), position="rope",
        rope_theta=float(config["rope_theta"]),
        mixers=("mla",) * len(feeds), ffn=tuple(feeds),
        routed=RoutedExperts(
            experts=config["n_routed_experts"], first=0, count=0,
            per_token=config["num_experts_per_tok"],
            groups=config["n_group"], groups_kept=config["topk_group"],
            scale=float(config["routed_scaling_factor"]),
            d_expert=config["moe_intermediate_size"],
            d_shared=config["moe_intermediate_size"]
            * config["n_shared_experts"]),
        latent=LatentAttention(
            latent=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
            rope=config["qk_rope_head_dim"], value=config["v_head_dim"],
            q_rank=config["q_lora_rank"], gate=False))


class Driver(docs.Driver):
    def __init__(self, cell, config, seed, reference):
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
        """Read between two steps of the engine's thread, under the lock a
        step holds: a step counts an admission's shared pages and its hit's
        tokens at two moments with the block table's upload between them,
        and a reading from this thread that fell there (0.64% of them on the
        chip, PERF.md section 6) parted the pair that ``check`` holds
        equal."""
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

    def warm(self):
        """Every program the window can reach, run once on the decoder: the
        pool's defragmentation (long plain prompts that retire under a short
        one, before anything is stored), every context registered (a miss
        each: the whole context through the chunk program, its pages stored),
        then one hit a window width the questions can pad to; then the
        engine's thread and the closed loop for ``ramp_seconds``."""
        rng = np.random.default_rng([self.seed, 2])
        vocab, w = self.config["vocab_size"], self.mix["warm"]

        def ask(key, doc, n):
            return (key, len(doc), np.concatenate(
                [doc, rng.integers(1, vocab, n).astype(np.int32)]),
                w["register_output"])
        t0 = time.perf_counter()
        moves = self.counters()["defrag_moves"]
        base.Driver.drive(self, rng, list(zip(w["defrag"]["prompts"],
                                              w["defrag"]["outputs"])))
        if self.counters()["defrag_moves"] == moves:
            raise RuntimeError("warm-up did not reach the pool's "
                               "defragmentation: the traffic file's "
                               "warm.defrag no longer provokes it")
        t1 = time.perf_counter()
        self.drive([ask(key, doc, w["questions"][0])
                    for key, doc in self.docs])
        t2 = time.perf_counter()
        before = self.counters()
        for n in w["questions"]:
            self.drive([ask(*self.docs[0], n)])
        moved = {k: v - before[k] for k, v in self.counters().items()}
        if (moved["prefix_hits"] != len(w["questions"])
                or moved["prefix_tokens_shared"] != moved["prefix_hit_tokens"]
                or moved["prefix_misses"]):
            raise RuntimeError(f"warm-up's hits did not share: {moved}")
        self.where.update(defrag_s=t1 - t0, register_s=t2 - t1,
                          warm_hits_s=time.perf_counter() - t2)
        print(json.dumps(dict(setup_where=self.where)), flush=True)
        self.engine.start()
        self.clients = [
            docs.Client(self.engine.address, plan, self.closing,
                        i * self.mix["start_stagger_s"])
            for i, plan in enumerate(self.plan)]
        for c in self.clients:
            c.start()
        time.sleep(self.mix["ramp_seconds"])

    def window(self, seconds):
        """``generate.Driver.window`` with the counters read once more, where
        a traced run's stretch ends: the windows' and the pool's counts OF
        THE STRETCH (``counters["kv_stats_traced"]``), not the window's
        scaled."""
        stretch = self.cell.get("trace_seconds") or seconds
        at = {}

        def mark():
            if self.engine is not None:     # a window shorter than the stretch
                at.update(self.counters())
        timer = threading.Timer(stretch, mark)
        before = self.counters()
        timer.start()
        try:
            result = base.Driver.window(self, seconds)
        finally:
            timer.cancel()
        result["counters"]["kv_stats_traced"] = {
            k: at[k] - before[k] for k in at}
        return result

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
            ("ticks_of_an_mla_layer_off_the_absorbed_kernel",
             m["attn_ticks_latent_window"]),
            ("latent_ticks_missing", 0 if m["attn_ticks_latent"] > 0 else 1),
            ("prefix_misses_in_window", m["prefix_misses"]),
            ("shared_tokens_short_of_the_contexts",
             abs(m["prefix_hit_tokens"] - m["prefix_tokens_shared"])),
            ("prefix_hits_missing", 0 if m["prefix_hits"] > 0 else 1)]
        return compared + [dict(name=k, value=v, limit=0) for k, v in exact]
