#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mmlspark_tpu still starts on the chip.

One process, JAX initialised once. Drives the three paths the paper is about
through their public entry points at real width, each compared with a plain
reference computed in the same process:

  A. transform — ResNet-50 ONNX through ``ONNXModel`` / ``DataFrame.transform``
  B. decode    — the 12-layer 768-wide decoder behind ``GenerationEngine``
                 (HTTP ``/generate``), then int8 KV pages on ``ContinuousDecoder``
  C. train     — ``LightGBMClassifier`` on a HIGGS-shaped 1M x 28 frame
  F. hybrid    — two layers of MiniCPM-SALA at their published widths (one
                 block-sparse, one lightning) on ``ContinuousDecoder``: a
                 document longer than ``dense_len`` registered as a prefix,
                 then a hit decodes a few tokens past it
  G. routed    — three layers of Ling-3.0-flash's share at their published
                 widths (kda under the dense feed-forward, kda and mla under
                 128 of 512 routed experts) on ``ContinuousDecoder``
  H. conv+gqa  — three layers of LFM2-24B-A2B's share at their published
                 widths (a gated short convolution under the dense
                 feed-forward, a grouped-query layer and a convolution under
                 all 64 routed experts) on ``ContinuousDecoder``

  I. latent    — three layers of GLM-4.7-Flash's share at their published
                 widths (latent attention under the dense feed-forward and
                 under all 64 routed experts with their shared expert) on
                 ``ContinuousDecoder``: a context registered as a prefix of
                 pages alone, then two callers on it at once
  J. state space — published layers 36-38 of Nemotron 3 Super's share at
                 their published widths (the 32/2 grouped-query layer without
                 positions, 128 of 512 relu^2 experts in the 1,024 latent
                 beside the shared expert, one Mamba-2 layer with no
                 feed-forward) on ``ContinuousDecoder``, then the state-space
                 step alone at the cell's shapes

``--chips 4`` runs instead ONLY the two paths that exist across chips and what
each is compared with: D. data-parallel GBDT over a 4-device ``data`` mesh,
E. the paged decoder mounted on a ``dp2 x tp2`` mesh.

No phase's failure is caught: the first failing check raises and the exit code
is non-zero. Each phase prints one JSON line (seconds are smoke timings of one
run, set-up/compile apart from the run — never a benchmark result); the LAST
line of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}`` as
JAX reports the device. The script refuses to run unless the platform is
``tpu``. ``--small`` is the one stated exemption: the same phases at toy sizes
for a CPU rehearsal in interpret mode, whose device line says ``cpu``.
"""

import argparse
import contextlib
import functools
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

AUC_TOL = 0.002          # Pallas vs segment_sum, 4 devices vs 1
TIE_TOL = 0.1            # a near-tie of two logits, in std-devs of the row
#: phase G's limit on the MEAN gap: against the float32 reference a bf16
#: forward swaps a near-tied 8th expert for some tokens, which moves single
#: tokens by up to two std-devs, so the widest gap says nothing there. Three
#: layers read 0.014-0.016 on the chip and the fp8 control 0.19 (PERF.md, PR 35)
ROUTED_GAP_MEAN = 0.05
#: phase H's limit on the mean gap, for the same reason and more of it: a
#: token takes 4 of 64 experts at a quarter of the weight each and no shared
#: expert steadies the layer, so one swapped 4th expert moves a token's logits
#: by half. Three layers (two routed) read 0.041 in the mean on the chip (0.053
#: on the CPU) and the fp8 control 0.53 (CPU, published widths; PERF.md,
#: PR 38): the geometric middle
CONV_GQA_GAP_MEAN = 0.15
#: phase I's: the same top-4 of 64, beside a shared expert that every token
#: takes and that steadies the layer. Three layers (two routed) read 0.0081 in
#: the mean on the chip (my chip run, PR 42; the widest gap 0.76); the limit
#: is phase G's
LATENT_GAP_MEAN = 0.05
#: phase J's: 22 of 512 experts a token at ~5/22 of the weight each beside a
#: shared expert, ONE routed layer of the three: a swapped 22nd expert moves
#: a token less than a swapped 4th of 64. The limit is phase G's; the chip's
#: reading is in PERF.md (PR 45)
SSM_GAP_MEAN = 0.05
QUANT_ERR_BOUND = 0.05   # tests/test_kv_quant.py's bound on the int8 probe
LOGIT_TOL = 0.06         # bf16 ResNet-50 logits vs float32, relative to max|ref|


def emit(**fields):
    print(json.dumps(fields), flush=True)


class Checks:
    """A phase's checks: every one is evaluated, the phase's line lists the
    ones that failed, and main() then raises on them."""

    def __init__(self):
        self.failed = []

    def require(self, cond, message):
        if not cond:
            self.failed.append(message)


def refuse(message):
    print(f"chip_smoke.py: {message}", file=sys.stderr, flush=True)
    raise SystemExit(2)


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def drain(decoder, tickets):
    """Step the decoder until every ticket is done. step() is called here,
    not serve_forever(): that loop contains a failing tick and carries on."""
    while not all(t.done for t in tickets):
        decoder.step()


def tick_text(decoder, compiled=False):
    """Lowered (or compiled) text of the decoder's greedy decode tick, from
    the arguments its last dispatch left behind."""
    lowered = decoder._tick.lower(
        decoder._params, decoder._tok, decoder._pos, decoder._active,
        decoder._kv.buffers, decoder._bt, decoder._remaining)
    return lowered.compile().as_text() if compiled else lowered.as_text()


def pool_programs(cfg, audit, shape=None):
    """``({program: lowered}, pool buffer shapes)``: the decode tick, one
    chunked-prefill extension and one group insertion as an engine of
    ``audit["slots"]`` slots compiles them, lowered at abstract shapes (no
    weights, no pool on the device). ``shape(dims, dtype)`` makes an
    argument's shape; a test passes one that places it on a described chip."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.zoo.transformer import init_transformer
    from mmlspark_tpu.serving import continuous as progs
    from mmlspark_tpu.serving.kv_pool import PagedKVPool
    shape = shape or jax.ShapeDtypeStruct
    slots, max_len = audit["slots"], audit["max_len"]
    page = progs.derived_page_size(cfg, max_len)    # as an engine built bare
    per_slot = -(-max_len // page)
    params = jax.tree.map(
        lambda a: shape(a.shape, cfg.dtype),
        jax.eval_shape(lambda: init_transformer(cfg, seed=0)))
    pool = PagedKVPool(cfg, page_size=page, residency=False, make_buffer=shape,
                       num_pages=1 + slots * per_slot + max(per_slot, slots))
    i32, f32, hd = jnp.int32, jnp.float32, cfg.d_model // cfg.heads
    ints = lambda *dims: shape(dims, i32)                   # noqa: E731
    g, n = audit["group"], audit["rows_len"]
    rows = [{kk: shape((g, cfg.heads, n, hd), cfg.dtype) for kk in "kv"}
            for _ in range(cfg.layers)]
    sample = lambda b: (shape((b,), f32), ints(b), shape((b,), f32),  # noqa: E731
                        shape((b, 2), jnp.uint32))
    lowered = {
        "jit_tick": progs._tick_program(
            cfg, page, max_len, 1, None, False, True).lower(
                params, ints(slots), ints(slots), shape((slots,), bool),
                pool.buffers, ints(slots, per_slot), ints(slots)),
        "jit__extend": progs._extend_program(cfg, page, max_len, True).lower(
            params, ints(1, audit["chunk"]), ints(1), pool.buffers,
            ints(1, per_slot)),
        "jit__insert_group": progs._insert_group_program(page, True).lower(
            pool.buffers, [], ints(g), rows, [], ints(g, -(-n // page)),
            ints(slots), ints(slots), shape((slots,), bool), ints(slots),
            ints(g), ints(g), ints(g), sample(slots), sample(g))}
    return lowered, {(b.dtype.name, b.shape)
                     for c in pool.buffers for b in c.values() if b.ndim == 4}


_COPY = re.compile(r"= (\w+)\[([\d,]+)\]\S* (?:copy|copy-start)\(")


def pool_copies(text, pool_shapes):
    """The ``copy`` instructions of an optimised HLO module whose result has
    the shape of a page-pool buffer: the pool copied, or laid out anew, by a
    program that was to update it in place."""
    names = {"bfloat16": "bf16", "float32": "f32", "int8": "s8",
             "float8_e4m3fn": "f8e4m3fn"}
    want = {(names.get(dt, dt), ",".join(map(str, dims)))
            for dt, dims in pool_shapes}
    return [line.strip() for line in text.splitlines()
            if (m := _COPY.search(line)) and m.groups() in want]


def phase_pool_in_place(sz, small):
    """Compile the three programs that carry the pool at GPT-2 XL's shapes
    and count the pool-sized copies in each: the in-place property's guard
    on the chip. On the CPU (``--small``) nothing is donated and the count
    says nothing, so it is printed and not required."""
    lowered, shapes = pool_programs(sz["pool_decoder"], sz["pool_audit"])
    return {name: len(pool_copies(low.compile().as_text(), shapes))
            for name, low in lowered.items()}


# ---------------------------------------------------------------------------
# sizes: the real ones, and the toy ones of --small


def sizes(small):
    import jax.numpy as jnp

    from mmlspark_tpu.models.zoo.resnet import RESNET50, ResNetConfig
    from mmlspark_tpu.models.zoo.transformer import TransformerConfig
    if small:
        return dict(
            resnet=ResNetConfig([1, 1, 1, 1], num_classes=10, width=8),
            image=32, rows=32, batch=8, ref_rows=4,
            decoder=TransformerConfig(vocab=256, layers=2, d_model=64,
                                      heads=4, d_ff=128, max_len=128,
                                      causal=True, norm="rmsnorm",
                                      position="rope", dtype=jnp.bfloat16),
            slots=4, max_len=128, max_new=6,
            engine_kw=dict(prefill_chunk=16),
            # phase F at toy widths: the tests' tiny hybrid, dense_len 64
            hybrid=dict(hidden_size=64, intermediate_size=128,
                        num_attention_heads=4, num_key_value_heads=2,
                        head_dim=16, lightning_nh=4, lightning_nkv=4,
                        lightning_head_dim=16, vocab_size=256,
                        dim_model_base=16, compute_dtype="float32",
                        param_dtype="float32",
                        sparse_config=dict(kernel_size=4, kernel_stride=2,
                                           block_size=8, topk=6,
                                           window_size=16, init_blocks=1,
                                           dense_len=64)),
            hybrid_len=160,
            # phase G at toy widths: the tests' tiny routed decoder
            routed=dict(hidden_size=64, intermediate_size=128,
                        num_attention_heads=4, head_dim=16,
                        qk_nope_head_dim=16, qk_rope_head_dim=8,
                        v_head_dim=16, kv_lora_rank=32,
                        moe_intermediate_size=32,
                        moe_shared_expert_intermediate_size=32, n_group=4,
                        topk_group=2, num_experts_per_tok=4, num_experts=8,
                        experts_held=[0, 8], published={"num_experts": 32},
                        vocab_size=256, compute_dtype="float32",
                        param_dtype="float32"),
            routed_len=128, routed_prompts=[9, 40, 70],
            # phase H at toy widths: the tests' tiny conv + gqa decoder
            conv_gqa=dict(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          moe_intermediate_size=32, num_experts=8,
                          num_experts_per_tok=2, experts_held=[0, 8],
                          vocab_size=256, compute_dtype="float32",
                          param_dtype="float32"),
            # phase I at toy widths: the tests' tiny all-latent decoder,
            # a context of three pages of 16
            latent=dict(hidden_size=64, intermediate_size=128,
                        num_attention_heads=5, num_key_value_heads=5,
                        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=12,
                        qk_rope_head_dim=8, v_head_dim=16,
                        moe_intermediate_size=32, n_routed_experts=8,
                        num_experts_per_tok=2, experts_held=[0, 8],
                        vocab_size=256, compute_dtype="float32",
                        param_dtype="float32"),
            latent_len=160, latent_context=48,
            # phase J at toy widths: the tests' tiny state-space decoder
            ssm=dict(hidden_size=64, num_attention_heads=16,
                     num_key_value_heads=1, head_dim=8, mamba_num_heads=8,
                     mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                     chunk_size=8, moe_latent_size=32,
                     moe_intermediate_size=48,
                     moe_shared_expert_intermediate_size=64,
                     n_routed_experts=8, experts_held=[0, 8],
                     published=dict(n_routed_experts=32),
                     num_experts_per_tok=6, vocab_size=256,
                     compute_dtype="float32", param_dtype="float32"),
            pool_decoder=TransformerConfig(vocab=256, layers=2, d_model=64,
                                           heads=4, d_ff=128, max_len=64,
                                           causal=True, dtype=jnp.bfloat16),
            pool_audit=dict(slots=2, max_len=64, chunk=16, group=2,
                            rows_len=32),
            prompt_lens=[5, 5, 9, 9, 12, 12, 40, 40],
            gbdt_rows=4096, gbdt_test=1024, gbdt_bins=255, gbdt_iters=5)
    return dict(
        resnet=RESNET50, image=224, rows=1024, batch=512, ref_rows=8,
        # scripts/bench_decode.py's real-width decoder: GPT-2-small-class,
        # Llama-style (RMSNorm + RoPE), bf16
        decoder=TransformerConfig(vocab=32000, layers=12, d_model=768,
                                  heads=12, d_ff=3072, max_len=2048,
                                  causal=True, norm="rmsnorm",
                                  position="rope", dtype=jnp.bfloat16),
        slots=16, max_len=2048, max_new=32, engine_kw={},
        # phase F: 8,384-token document (dense_len 8,192), pages of 64
        hybrid_len=8704,
        # phase G: prompts across a chunk's end, 4 slots of 1,024 positions
        routed_len=1024, routed_prompts=[40, 300, 520],
        # phase I: a 2,560-token context (ten pages of 256: past one
        # 2,048-key tile of the window's fold), 4 slots of 4,096 positions
        latent_len=4096, latent_context=2560,
        # the generation cell's decoder and engine (benchmarks/configs/
        # gpt2_xl.json, workloads/gpt2xl_generate_closed.json): GPT-2 XL,
        # 8 slots of 1024 positions in the pages the decoder derives from
        # that length (64: 16 a slot), 256-token chunks
        pool_decoder=TransformerConfig(vocab=50257, layers=48, d_model=1600,
                                       heads=25, d_ff=6400, max_len=1024,
                                       causal=True, norm="layernorm",
                                       position="learned", dtype=jnp.bfloat16),
        pool_audit=dict(slots=8, max_len=1024, chunk=256, group=2,
                        rows_len=128),
        # one group is longer than the engine's default prefill_chunk (256)
        prompt_lens=[12, 12, 12, 64, 64, 64, 300, 300],
        gbdt_rows=1_000_000, gbdt_test=100_000, gbdt_bins=255, gbdt_iters=5)


# ---------------------------------------------------------------------------
# F. hybrid (the cell sala_docqa_closed8's model, two layers of it)


def phase_hybrid(sz, seed, small):
    """A document past ``dense_len`` goes in as a prefix miss (chunked
    prefill, pages and state snapshot stored), then the same document with
    another question is a hit: snapshot restored, pages shared, the sparse
    layer selecting blocks in every tick. The hit's tokens are judged by the
    benchmark's plain float32 reference, teacher-forced."""
    from benchmarks import run as bench_run
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    ck = Checks()
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "minicpm_sala_l8.json")) as fh:
        config = json.load(fh)
    config.update(num_hidden_layers=2, mixer_types=config["mixer_types"][:2])
    if small:
        config.update(sz["hybrid"])
    reference = bench_run.load_by_path("references", config["reference"])
    cfg = bench_run.load_by_path("drivers", "generate_docs").program_config(
        config, sz["hybrid_len"])
    t0 = time.perf_counter()
    params = reference.make_weights(config, seed)
    # no page_size: a model with sparse layers is served in pages of its
    # sparse block
    dec = ContinuousDecoder(params, cfg, max_slots=2,
                            max_len=sz["hybrid_len"], **sz["engine_kw"])
    ck.require(dec._page == config["sparse_config"]["block_size"],
               f"page {dec._page} is not the sparse block")
    rng = np.random.default_rng(seed)
    dense_len = config["sparse_config"]["dense_len"]
    doc = rng.integers(1, cfg.vocab, dense_len + 3 * dec._page).astype(
        np.int32)
    served = []
    for n in (24, 40):                          # a miss, then a hit
        prompt = np.concatenate(
            [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)])
        req = dec.submit(prompt, sz["max_new"], prefix_key="doc",
                         prefix_len=doc.size)
        drain(dec, [req])
        dec.result(req)
        served.append((prompt, req.tokens))
    run_s = time.perf_counter() - t0
    stats = dec._kv.stats
    ck.require(dec.stats["prefix_hits"] == 1
               and stats.get("state_snapshots_restored") == 1,
               f"the second request was no restored hit: {dec.stats}")
    ck.require(stats["attn_ticks_sparse"] > 0 and not stats["attn_ticks_gather"],
               f"no tick selected blocks on the kernel: {stats}")
    t0 = time.perf_counter()
    gaps = np.concatenate([reference.served_token_gaps(
        params, config, p, o, sz["hybrid_len"]) for p, o in served])
    ck.require(float(gaps.max()) <= TIE_TOL,
               f"a served token lies {gaps.max():.3f} std-devs under the "
               f"float32 reference's best (limit {TIE_TOL})")
    return ck, dict(hybrid_run_s=run_s,
                    hybrid_reference_s=time.perf_counter() - t0,
                    context=int(doc.size), gap_max=float(gaps.max()),
                    gap_mean=float(gaps.mean()),
                    attn_ticks_sparse=stats["attn_ticks_sparse"],
                    snapshot_bytes=dec._kv.snapshot_bytes)


# ---------------------------------------------------------------------------
# G. routed (the cell lingflash_reason_closed32's model, three layers of it)


def serve_share(sz, seed, small, config_file, driver, cut, small_sizes):
    """What phases G and H share: ``config_file`` cut to three layers
    (``cut``; at toy widths under ``--small``) on ``ContinuousDecoder``,
    three prompts prefilled in chunks and decoded together, the served tokens
    judged by the configuration's plain float32 reference, teacher-forced.
    Returns ``(pool stats, prompts, gaps, detail)``."""
    from benchmarks import run as bench_run
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    with open(os.path.join(REPO, "benchmarks", "configs", config_file)) as fh:
        config = json.load(fh)
    config.update(dict(num_hidden_layers=3, layers_held=[0, 10, 11]), **cut)
    if small:
        config.update(small_sizes)
    reference = bench_run.load_by_path("references", config["reference"])
    cfg = bench_run.load_by_path("drivers", driver).program_config(
        config, sz["routed_len"])
    t0 = time.perf_counter()
    params = reference.make_weights(config, seed)
    dec = ContinuousDecoder(params, cfg, max_slots=4,
                            max_len=sz["routed_len"], **sz["engine_kw"])
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32)
               for n in sz["routed_prompts"]]
    reqs = [dec.submit(p, sz["max_new"]) for p in prompts]
    drain(dec, reqs)
    served = [(p, dec.result(r)) for p, r in zip(prompts, reqs)]
    run_s = time.perf_counter() - t0
    stats = dec._kv.stats
    t0 = time.perf_counter()
    gaps = np.concatenate([reference.served_token_gaps(
        params, config, p, o, sz["routed_len"]) for p, o in served])
    return stats, prompts, gaps, dict(
        run_s=run_s, reference_s=time.perf_counter() - t0,
        gap_max=float(gaps.max()), gap_mean=float(gaps.mean()),
        page=dec._page, moe={k[4:]: int(v) for k, v in stats.items()
                             if k.startswith("moe_")})


def require_gap_mean(ck, gaps, limit):
    ck.require(float(gaps.mean()) <= limit,
               f"the served tokens lie {gaps.mean():.4f} std-devs under the "
               f"float32 reference's best in the mean (limit {limit})")


def phase_routed(sz, seed, small):
    """Layers 0, 10 and 11 of the routed configuration at its published
    widths (a kda layer under the dense feed-forward, a kda and an mla layer
    under 128 of 512 routed experts): every tick on the delta-rule step, the
    absorbed latent kernel and the grouped product, no pair dropped; the
    tokens are judged in the mean (:data:`ROUTED_GAP_MEAN`)."""
    ck = Checks()
    stats, _, gaps, detail = serve_share(
        sz, seed, small, "ling3_flash_ep4_l7.json", "generate_ling", {},
        sz.get("routed"))
    ck.require(stats.get("attn_ticks_kda", 0) > 0
               and stats.get("attn_ticks_latent", 0) > 0
               and not stats["attn_ticks_gather"],
               f"a tick left the kda step or the latent kernel: {stats}")
    ck.require(stats.get("moe_pairs_held", 0) > 0
               and stats["moe_pairs_dropped"] == 0
               and stats["moe_pairs_misplaced"] == 0,
               f"routed pairs dropped or misplaced: {stats}")
    require_gap_mean(ck, gaps, ROUTED_GAP_MEAN)
    return ck, dict(detail,
                    attn_ticks_kda=stats.get("attn_ticks_kda", 0),
                    attn_ticks_latent=stats.get("attn_ticks_latent", 0))


EXPERTS_ALONE_TOL = 1e-2


def experts_product_alone(ck, seed, small):
    """The grouped expert product ALONE at the shapes of
    ``lfm2_ragchat_closed32`` (64 experts of (2048, 3072) + (1536, 2048)
    bf16; toy shapes under ``--small``), on two layouts: a plain tick's (one
    tile an expert, a product a tile) and a carrying step's (runs of 1, 2, 3,
    ``RUN`` and ``RUN + 1`` tiles, a product a run), each with experts no row
    reached (their weights NaN) and tiles past the bound. Rows under the
    bound against a float32 product of the same bf16 operands; on the chip,
    microseconds an expert read, eight calls (the cell's routed layers) a
    program, beside the 23 us of an expert's bytes at the chip's peak."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.grouped_matmul import RUN, TILE, grouped_swiglu

    E, D, F = (12, 128, 64) if small else (64, 2048, 1536)
    layers = 8
    interpret = jax.devices()[0].platform != "tpu"
    k = jax.random.split(jax.random.key(seed), 3)
    gu = jax.random.normal(k[0], (E, D, 2 * F), jnp.bfloat16) * D ** -0.5
    dn = jax.random.normal(k[1], (E, F, D), jnp.bfloat16) * F ** -0.5
    layouts = {"tick": [1] * (E - 4) + [0] * 4,
               "carrying": ([1, 2, 3, RUN, RUN + 1, 0] * E)[:E]}

    @jax.jit
    def oracle(x, e, gu, dn):
        h = jnp.dot(x.astype(jnp.float32), gu[e].astype(jnp.float32),
                    precision="highest")
        h = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(jnp.bfloat16)
        return jnp.dot(h.astype(jnp.float32), dn[e].astype(jnp.float32),
                       precision="highest")

    detail = {}
    for name, tiles in layouts.items():
        tiles = np.asarray(tiles)
        idle = jnp.asarray(tiles == 0)[:, None, None]
        total, spare = int(tiles.sum()), 3
        experts = jnp.asarray(np.concatenate(
            [np.repeat(np.arange(E), tiles), np.full(spare, E - 1)]), jnp.int32)
        xs = jax.random.normal(k[2], (layers, (total + spare) * TILE, D),
                               jnp.bfloat16)
        args = (jnp.where(idle, jnp.nan, gu), jnp.where(idle, jnp.nan, dn))
        prog = jax.jit(lambda xs, gu_, dn_: [grouped_swiglu(
            x, experts, total, gu_, dn_) for x in xs])
        got = np.asarray(prog(xs, *args)[0])[:total * TILE]
        want = np.concatenate([np.asarray(oracle(
            xs[0][s * TILE:(s + 1) * TILE], e, gu, dn))
            for s, e in enumerate(np.asarray(experts)[:total])])
        ck.require(np.isfinite(got).all(),
                   f"{name}: the product read an expert no row reached")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        ck.require(err < EXPERTS_ALONE_TOL,
                   f"{name}: the product alone is {err:.2e} of the rows' "
                   f"scale from the float32 product")
        detail[name] = dict(tiles=total, experts=int((tiles > 0).sum()),
                            steps=int(-(-tiles // RUN).sum()), err=err)
        if interpret:
            continue                    # a CPU run gives counts, never speeds
        laps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(prog(xs, *args))
            laps.append(time.perf_counter() - t0)
        detail[name]["us_an_expert"] = (
            min(laps) / (layers * detail[name]["experts"]) * 1e6)
    return detail


def phase_conv_gqa(sz, seed, small):
    """Layers 0, 10 and 11 of the conv + grouped-query configuration at its
    published widths (a gated short convolution under the dense
    feed-forward, a grouped-query layer and a convolution under all 64
    routed experts): every tick on the grouped-query kernel and the grouped
    product, every pair held, none dropped, the prompts' tokens counted; the
    tokens are judged in the mean (:data:`CONV_GQA_GAP_MEAN`)."""
    ck = Checks()
    stats, prompts, gaps, detail = serve_share(
        sz, seed, small, "lfm2_24b_a2b_pp5_l9.json", "generate_lfm2",
        dict(layer_types=["conv", "full_attention", "conv"]),
        sz.get("conv_gqa"))
    ck.require(stats.get("attn_ticks_gqa", 0) > 0
               and stats.get("attn_ticks_conv", 0) > 0
               and not stats.get("attn_ticks_gqa_window", 0)
               and not stats["attn_ticks_gather"],
               f"a tick left the grouped-query kernel: {stats}")
    ck.require(stats.get("moe_pairs_held", 0) > 0
               and stats["moe_pairs_held"] == stats["moe_pairs_routed"]
               and stats["moe_pairs_dropped"] == 0
               and stats["moe_pairs_misplaced"] == 0,
               f"routed pairs dropped, misplaced or not held: {stats}")
    ck.require(stats["prefill_tokens"] == sum(len(p) for p in prompts),
               f"prefill_tokens {stats['prefill_tokens']}")
    require_gap_mean(ck, gaps, CONV_GQA_GAP_MEAN)
    return ck, dict(detail,
                    attn_ticks_gqa=stats.get("attn_ticks_gqa", 0),
                    attn_ticks_conv=stats.get("attn_ticks_conv", 0),
                    product_alone=experts_product_alone(ck, seed, small))


# ---------------------------------------------------------------------------
# J. state space (the cell nemotronsuper_chat_closed32's model, three
# published layers of it)


def ssm_step_alone(ck, seed, small):
    """The state-space step ALONE at the cell's shapes (32 rows of 128 heads
    of 64 on a state 128 wide in 8 groups; toy shapes under ``--small``): one
    step against the plain recurrence in float32, an inactive row's state
    untouched, and (on the chip) its seconds a call over five calls a
    program, the cell's five ``M`` layers, beside the bytes a call must move
    at the chip's peak."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.ssm_step import (pack_state, ssm_decode_step,
                                           unpack_state)
    B, H, P, N, G = (3, 8, 8, 16, 2) if small else (32, 128, 64, 128, 8)
    k = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(k[0], (B, H, P))
    d = jax.random.uniform(k[1], (B, H), minval=0.001, maxval=0.3)
    a = jnp.exp(-d * jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0))
    b, c = (jax.random.normal(kk, (B, G, N)) for kk in k[3:5])
    state = jax.random.normal(k[5], (B, H, P, N))
    active = jnp.arange(B) != 1
    y, new = ssm_decode_step(u * d[..., None], a, b, c, pack_state(state),
                             active)
    bh, ch = (jnp.repeat(t, H // G, axis=1) for t in (b, c))
    want = (a[..., None, None] * state
            + (u * d[..., None])[..., None] * bh[:, :, None, :])
    want_y = jnp.einsum("bhpn,bhn->bhp", want, ch,
                        precision=jax.lax.Precision.HIGHEST)
    new = unpack_state(new)
    err = float(jnp.abs(jnp.where(active[:, None, None], y - want_y,
                                  0.0)).max())
    err_s = float(jnp.abs(jnp.where(active[:, None, None, None], new - want,
                                    0.0)).max())
    ck.require(err < 1e-4 and err_s < 1e-5,
               f"the step is {err:.2e} / {err_s:.2e} from the recurrence")
    ck.require(bool(jnp.array_equal(new[1], state[1])),
               "an inactive row's state moved")
    detail = dict(rows=B, heads=H, y_err=err, state_err=err_s)
    if not small:
        calls = 5

        @jax.jit
        def many(du, a_, b_, c_, st):
            for _ in range(calls):
                y_, st = ssm_decode_step(du, a_, b_, c_, st, active)
                du = du + 1e-3 * y_
            return du, st
        args = (u * d[..., None], a, b, c, pack_state(state))
        jax.block_until_ready(many(*args))
        t0 = time.perf_counter()
        for _ in range(20):
            out = many(*args)
        jax.block_until_ready(out)
        per_call = (time.perf_counter() - t0) / (20 * calls)
        nbytes = 2 * (B - 1) * H * P * N * 4    # the live rows, in and out
        detail.update(ms_a_call=1e3 * per_call,
                      share_of_819_GB_s=nbytes / 819e9 / per_call)
    return detail


def phase_ssm(sz, seed, small):
    """Published layers 36, 37 and 38 of the state-space configuration at
    its published widths (the grouped-query layer at 32 heads over 2, 128 of
    512 relu^2 experts in the 1,024 latent beside the shared expert, one
    Mamba-2 layer with no feed-forward after it): every tick on the
    state-space step, the grouped-query kernel and the grouped product, no
    pair dropped; the tokens are judged in the mean (:data:`SSM_GAP_MEAN`);
    then the step alone at the cell's shapes."""
    ck = Checks()
    stats, prompts, gaps, detail = serve_share(
        sz, seed, small, "nemotron3_super_ep4_l11.json", "generate_nemotron",
        dict(hybrid_override_pattern="*EM", layers_held=[36, 37, 38]),
        sz.get("ssm"))
    ck.require(stats.get("attn_ticks_ssm", 0) > 0
               and stats.get("attn_ticks_gqa", 0) > 0
               and not stats.get("attn_ticks_ssm_window", 0)
               and not stats.get("attn_ticks_gqa_window", 0)
               and not stats["attn_ticks_gather"],
               f"a tick left the state-space step or the grouped-query "
               f"kernel: {stats}")
    ck.require(stats.get("moe_pairs_held", 0) > 0
               and stats["moe_pairs_dropped"] == 0
               and stats["moe_pairs_misplaced"] == 0,
               f"routed pairs dropped or misplaced: {stats}")
    ck.require(stats["ssm_state_rows"] > 0
               and stats["prefill_tokens"] == sum(len(p) for p in prompts),
               f"ssm_state_rows {stats['ssm_state_rows']}, prefill_tokens "
               f"{stats['prefill_tokens']}")
    require_gap_mean(ck, gaps, SSM_GAP_MEAN)
    return ck, dict(detail, step_alone=ssm_step_alone(ck, seed, small),
                    attn_ticks_ssm=stats.get("attn_ticks_ssm", 0),
                    attn_ticks_gqa=stats.get("attn_ticks_gqa", 0),
                    ssm_state_rows=stats["ssm_state_rows"])


# ---------------------------------------------------------------------------
# I. latent (the cell glmflash_repoctx_shared32's model, three layers of it)


#: the absorbed kernel alone against a float32 oracle, relative L2 of a row's
#: contexts: on the chip the MXU rounds the float32 query and weights to bf16
#: (2**-8 a value), in interpret mode nothing does
LATENT_SWEEP_TOL = 1e-2


def latent_sweep_alone(ck, seed, small):
    """The absorbed latent kernel ALONE at the shapes of
    ``glmflash_repoctx_shared32``: 32 rows of 20 float32 heads over 640-wide
    bf16 latent pages of 256 positions, 128 a slot, a pool of 1,024; eight
    stored contexts of 64-120 pages, four rows on each BY REFERENCE, a few
    pages of a row's own behind them. Every page no row needs holds NaN, the
    trash page 0 and the table's stale entries with it. Three seeds against a
    float32 oracle made from the same pages, and against the one-page sweep
    (``k`` = 1); then microseconds a page a call, seven calls (the cell's
    layers) a program, the one-page sweep beside the blocks the call picks
    and beside twice as many."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import paged_attention as pa

    if small:
        B, H, page, P, N, dk, dv, docs = 8, 5, 16, 32, 96, 128, 96, (9, 18)
    else:
        B, H, page, P, N, dk, dv, docs = (32, 20, 256, 128, 1024, 640, 512,
                                          tuple(64 + 8 * i for i in range(8)))
    scale, layers = 256 ** -0.5, 7
    interpret = jax.devices()[0].platform != "tpu"

    def case(seed):
        rng = np.random.default_rng(seed)
        free = iter(rng.permutation(np.arange(1, N)))
        stored = [[next(free) for _ in range(n)] for n in docs]
        bt = np.zeros((B, P), np.int32)         # stale entries: trash page 0
        lengths = np.zeros(B, np.int32)
        for b in range(B):
            doc = stored[b * len(docs) // B]
            own = int(rng.integers(1, 5))
            lengths[b] = (len(doc) + own) * page - int(rng.integers(0, page))
            bt[b, :len(doc) + own] = doc + [next(free) for _ in range(own)]
        lengths[rng.integers(0, B)] -= lengths.min() % page + 1  # an edge
        pool = rng.normal(0, 1, (N, 1, page, dk)).astype(np.float32)
        live = np.zeros(N, bool)
        for b in range(B):
            live[bt[b, :-(-lengths[b] // page)]] = True
        pool[~live] = np.nan
        q = rng.normal(0, 1, (B, H, dk)).astype(np.float32)
        return (jnp.asarray(q), jnp.asarray(pool, jnp.bfloat16),
                jnp.asarray(bt), jnp.asarray(lengths))

    @jax.jit
    def oracle(q, pool, bt, lengths):
        def row(args):
            q, bt, n = args
            rows = pool[bt, 0].reshape(P * page, dk).astype(jnp.float32)
            ok = jnp.arange(P * page) < n
            rows = jnp.where(ok[:, None], rows, 0.0)
            s = jnp.einsum("hd,kd->hk", q, rows, precision="highest") * scale
            p = jax.nn.softmax(jnp.where(ok[None, :], s, -jnp.inf), axis=-1)
            return jnp.einsum("hk,kd->hd", p, rows[:, :dv],
                              precision="highest")
        return jax.lax.map(row, (q, bt, lengths))

    shipped = functools.partial(pa.paged_attention_latent, v_width=dv,
                                scale=scale)

    def launch(k):
        def one(q, pool, bt, lengths):
            q = jnp.pad(q, ((0, 0), (0, -H % 8), (0, 0)))[:, None]
            return pa._latent_launch(q, pool, bt, lengths, v_width=dv,
                                     scale=scale, interpret=interpret,
                                     k=k)[:, 0, :H]
        return one

    errs, apart, one_page = [], [], jax.jit(launch(1))
    for sd in (seed, seed + 1, seed + 2):
        args = case(sd)
        want, got = np.asarray(oracle(*args)), np.asarray(shipped(*args))
        one = np.asarray(one_page(*args))
        ck.require(np.isfinite(got).all(),
                   f"the absorbed kernel alone folded a NaN page (seed {sd})")
        errs.append(float(np.max(np.linalg.norm(got - want, axis=-1)
                                 / np.linalg.norm(want, axis=-1))))
        apart.append(float(np.max(np.abs(got - one))))
    ck.require(max(errs) < LATENT_SWEEP_TOL,
               f"the absorbed kernel alone misses the float32 oracle: {errs}")

    args = case(seed)
    k = pa.latent_block(args[1][0].nbytes, P)
    pages = int(np.sum(-(-np.asarray(args[3]) // page)))
    qs = jnp.stack([args[0] * (1 + i) for i in range(layers)])
    timed = {}
    for name, fn in (("one_page", launch(1)), ("shipped", shipped),
                     (f"k{2 * k}", launch(2 * k))):
        prog = jax.jit(lambda qs, *rest, fn=fn: sum(
            fn(q, *rest) for q in qs))
        ref = np.asarray(prog(qs, *args[1:]))         # compiles
        ck.require(np.isfinite(ref).all(), f"{name}: a NaN page was folded")
        if interpret:
            continue                    # a CPU run gives counts, never speeds
        laps = []
        for _ in range(5):
            t0 = time.perf_counter()
            prog(qs, *args[1:]).block_until_ready()
            laps.append(time.perf_counter() - t0)
        timed[name] = dict(us_a_page=min(laps) / (layers * pages) * 1e6,
                           ms_a_call=min(laps) / layers * 1e3)
    return dict(pages_a_step=k, pages=pages, rows=B,
                oracle_rel_l2=errs, apart_from_one_page=apart, **timed)


def phase_latent(sz, seed, small):
    """Layers 0, 10 and 11 of the all-latent configuration at its published
    widths (20 heads of 192 + 64 / 256 under a rank-768 query; the dense
    feed-forward, then 64 routed experts beside a shared one): a context past
    one tile of the window's fold goes in as a prefix miss (pages stored,
    nothing a slot to snapshot), then two callers ask about it at once:
    both hits, the context's pages in both block tables by reference, every
    tick on the absorbed kernel with 20 heads padded to 24, every pair held.
    All three requests' tokens are judged in the mean
    (:data:`LATENT_GAP_MEAN`)."""
    from benchmarks import run as bench_run
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    ck = Checks()
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm47_flash_l7.json")) as fh:
        config = json.load(fh)
    config.update(num_hidden_layers=3, layers_held=[0, 10, 11])
    if small:
        config.update(sz["latent"])
    reference = bench_run.load_by_path("references", config["reference"])
    cfg = bench_run.load_by_path("drivers", "generate_glm").program_config(
        config, sz["latent_len"])
    t0 = time.perf_counter()
    params = reference.make_weights(config, seed)
    dec = ContinuousDecoder(params, cfg, max_slots=4,
                            max_len=sz["latent_len"], **sz["engine_kw"])
    rng = np.random.default_rng(seed)
    doc = rng.integers(1, cfg.vocab, sz["latent_context"]).astype(np.int32)
    served = []
    for group in ((24,), (40, 9)):              # a miss, then two hits at once
        prompts = [np.concatenate(
            [doc, rng.integers(1, cfg.vocab, n).astype(np.int32)])
            for n in group]
        reqs = [dec.submit(p, sz["max_new"], prefix_key="ctx",
                           prefix_len=doc.size) for p in prompts]
        drain(dec, reqs)
        served += [(p, dec.result(r)) for p, r in zip(prompts, reqs)]
    run_s = time.perf_counter() - t0
    stats = dec._kv.stats
    ck.require(doc.size % dec._page == 0
               and dec.stats["prefix_hits"] == 2
               and stats["prefix_tokens_shared"] == 2 * doc.size
               and not any(k.startswith("state_snapshot") for k in stats),
               f"the two callers did not share the context's pages alone: "
               f"{dec.stats} {stats}")
    ck.require(stats.get("attn_ticks_latent", 0) > 0
               and not stats.get("attn_ticks_latent_window", 0)
               and not stats["attn_ticks_gather"],
               f"a tick left the absorbed latent kernel: {stats}")
    ck.require(stats["latent_window_keys"]
               >= stats["latent_window_context"] > 3 * doc.size,
               f"the windows' keys were not counted: {stats}")
    ck.require(stats.get("moe_pairs_held", 0) > 0
               and stats["moe_pairs_held"] == stats["moe_pairs_routed"]
               and stats["moe_pairs_dropped"] == 0
               and stats["moe_pairs_misplaced"] == 0,
               f"routed pairs dropped, misplaced or not held: {stats}")
    t0 = time.perf_counter()
    gaps = np.concatenate([reference.served_token_gaps(
        params, config, p, o, sz["latent_len"]) for p, o in served])
    require_gap_mean(ck, gaps, LATENT_GAP_MEAN)
    reference_s = time.perf_counter() - t0
    return ck, dict(
        run_s=run_s, reference_s=reference_s,
        sweep_alone=latent_sweep_alone(ck, seed, small),
        gap_max=float(gaps.max()), gap_mean=float(gaps.mean()),
        page=dec._page, context=int(doc.size), tile=dec._accountants[0].tile,
        attn_ticks_latent=stats["attn_ticks_latent"],
        latent_window_keys=stats["latent_window_keys"],
        prefix_tokens_shared=stats["prefix_tokens_shared"],
        moe={k[4:]: int(v) for k, v in stats.items()
             if k.startswith("moe_")})


# ---------------------------------------------------------------------------
# A. transform


def phase_transform(sz, seed, small):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.zoo.resnet import (ResNetConfig,
                                                export_resnet_onnx,
                                                init_resnet, resnet_apply)
    from mmlspark_tpu.ops.compile_cache import M_STEADY_RECOMPILES

    t0 = time.perf_counter()
    cfg, side, n, batch = sz["resnet"], sz["image"], sz["rows"], sz["batch"]
    mean, std = [0.485, 0.456, 0.406], [0.229, 0.224, 0.225]
    model = ONNXModel(export_resnet_onnx(cfg, seed=seed, input_size=side),
                      feed_dict={"input": "image"},
                      fetch_dict={"logits": "logits"},
                      argmax_dict={"pred": "logits"},
                      transpose_dict={"input": [0, 3, 1, 2]},
                      normalize_dict={"input": {"scale": 1.0 / 255.0,
                                                "mean": mean, "std": std}},
                      mini_batch_size=batch, compute_dtype="bfloat16")
    images = np.random.default_rng(seed).integers(
        0, 256, (n, side, side, 3), dtype=np.uint8)
    col = np.empty(n, dtype=object)
    for i in range(n):
        col[i] = images[i]
    df = DataFrame({"image": col}, npartitions=2)
    warm = model.warm_up(batch_sizes=[batch],
                         input_specs={"input": (np.uint8, (side, side, 3))})
    setup_s = time.perf_counter() - t0

    recompiles_before = M_STEADY_RECOMPILES.labels().get()
    t0 = time.perf_counter()
    out = model.transform(df)
    logits = np.stack([np.asarray(r, np.float32) for r in out["logits"]])
    pred = np.asarray(out["pred"]).astype(np.int64)
    run_s = time.perf_counter() - t0
    recompiles = M_STEADY_RECOMPILES.labels().get() - recompiles_before

    # plain reference: the zoo's own NHWC forward in float32, same weights
    k = sz["ref_rows"]
    ref_cfg = ResNetConfig(cfg.stage_sizes, cfg.num_classes, cfg.width,
                           dtype=jnp.float32)
    x = (images[:k].astype(np.float32) / 255.0 - np.asarray(mean, np.float32)
         ) / np.asarray(std, np.float32)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        ref = np.asarray(jax.jit(lambda w, im: resnet_apply(w, im, ref_cfg))(
            init_resnet(cfg, seed), jnp.asarray(x)))
    reference_s = time.perf_counter() - t0
    err = float(np.max(np.abs(logits[:k] - ref)) / np.max(np.abs(ref)))
    # a top-1 flip counts only where the reference itself separates the two
    # classes by more than the logit tolerance
    picked = ref[np.arange(k), pred[:k]]
    margin = float(np.max(ref.max(axis=1) - picked) / np.max(np.abs(ref)))

    ck = Checks()
    ck.require(logits.shape == (n, cfg.num_classes), f"logits {logits.shape}")
    ck.require(np.isfinite(logits).all(), "non-finite logits")
    ck.require((pred == logits.argmax(axis=1)).all(), "pred != argmax(logits)")
    ck.require(err < LOGIT_TOL,
               f"logits off the float32 reference by {err:.4f}")
    ck.require(margin < LOGIT_TOL, f"top-1 off the reference by {margin:.4f}")
    ck.require(recompiles == 0, f"{recompiles} steady-state recompiles")
    return ck, dict(setup_s=setup_s, run_s=run_s, reference_s=reference_s,
                    rows=n, batch=batch,
                    partitions=df.npartitions, warm_up=warm,
                    logits_rel_err=err, top1_margin=margin,
                    top1_equal=int((pred[:k] == ref.argmax(axis=1)).sum()),
                    ref_rows=k, steady_state_recompiles=int(recompiles))


# ---------------------------------------------------------------------------
# B. serve / decode


def make_prompts(sz, seed):
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(1, sz["decoder"].vocab, n).astype(np.int32)
            for n in sz["prompt_lens"]]


def oracle_tokens(params, cfg, prompts, max_new):
    """``generate_cached`` (the parity oracle), one call per prompt length."""
    from mmlspark_tpu.models.zoo.transformer import generate_cached
    want = [None] * len(prompts)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        ids = np.asarray(generate_cached(
            params, np.stack([prompts[i] for i in idx]), cfg,
            max_new_tokens=max_new))
        for row, i in enumerate(idx):
            want[i] = [int(t) for t in ids[row, n:]]
    return want


def first_divergence(got, want):
    return [next((j for j, (a, b) in enumerate(zip(g, w)) if a != b), None)
            if g != w else None for g, w in zip(got, want)]


@functools.lru_cache(maxsize=None)
def reference_forward(cfg):
    """Jitted float32 ``transformer_apply`` for ``cfg``, built once so the
    engine's and the oracle's tokens share one compile."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.zoo.transformer import transformer_apply
    f32 = cfg._replace(dtype=jnp.float32)
    return jax.jit(lambda w, x: transformer_apply(w, x, f32))


def reference_gaps(params, cfg, prompts, outputs):
    """The plain reference for greedy decoding: one float32 causal forward
    of ``transformer_apply`` over prompt + emitted tokens (teacher forcing).
    Per request, the largest amount by which an emitted token's logit falls
    short of the row's best, in standard deviations of the row: 0.0 means
    every token is the float32 argmax given the same history."""
    import jax
    import jax.numpy as jnp
    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        ids[i, :len(p) + len(o)] = np.concatenate([p, np.asarray(o, np.int32)])
    with jax.default_matmul_precision("float32"):
        hidden = reference_forward(cfg)(params, jnp.asarray(ids))
        gaps = []
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            rows = hidden[i, len(p) - 1:len(p) - 1 + len(o)]
            logits = np.asarray(rows.astype(jnp.float32)
                                @ jnp.asarray(params["lm_head"]["w"]))
            short = logits.max(axis=1) - logits[np.arange(len(o)), o]
            gaps.append(float(np.max(short / logits.std(axis=1))))
    return gaps


def post_generate(url, prompts, max_new):
    results = [None] * len(prompts)

    def client(i):
        req = urllib.request.Request(
            url, data=json.dumps({"tokens": [int(t) for t in prompts[i]],
                                  "max_new": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=900.0) as r:
            results[i] = (r.status, json.loads(r.read()))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def phase_decode(sz, seed, small):
    import jax

    from mmlspark_tpu.models.zoo.transformer import init_transformer
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    from mmlspark_tpu.serving.generation import GenerationEngine

    ck = Checks()
    t0 = time.perf_counter()
    cfg, max_new = sz["decoder"], sz["max_new"]
    params = jax.device_put(init_transformer(cfg, seed=seed))
    prompts = make_prompts(sz, seed)
    want = oracle_tokens(params, cfg, prompts, max_new)
    oracle_s = time.perf_counter() - t0

    # every choice of implementation left at its default (paged_attn unset)
    t0 = time.perf_counter()
    engine = GenerationEngine(params, cfg, max_slots=sz["slots"],
                              max_len=sz["max_len"], reply_timeout=900.0,
                              **sz["engine_kw"])
    with engine:
        # set-up: one short round over the same prompt lengths compiles the
        # prefill buckets and the tick
        post_generate(engine.address, prompts, 2)
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        replies = post_generate(engine.address, prompts, max_new)
        run_s = time.perf_counter() - t0
        decoder = engine.decoder
        impl = decoder._attn_impl
        kernel_compiled = "tpu_custom_call" in tick_text(decoder)
        ticks = dict(kernel=decoder._kv.stats.get("attn_ticks_kernel", 0),
                     gather=decoder._kv.stats.get("attn_ticks_gather", 0))
        geometry = {k: decoder._kv.stats[k]
                    for k in ("page_size", "pages_per_slot")}
    if not all(r is not None and r[0] == 200 for r in replies):
        raise RuntimeError(f"HTTP replies: {[r and r[0] for r in replies]}")
    got = [r[1]["tokens"] for r in replies]
    # bf16 rounds the engine's chunked prefill and the oracle's token-by-token
    # prefill differently, so two near-tied logits may swap: a request may
    # leave generate_cached's tokens only at such a tie, which the float32
    # reference decides — every token of BOTH must be its argmax or within
    # TIE_TOL of it
    gaps = reference_gaps(params, cfg, prompts, got)
    oracle_gaps = reference_gaps(params, cfg, prompts, want)
    ck.require(impl == "kernel" and ticks["kernel"] > 0
               and ticks["gather"] == 0,
               f"default decoder did not run the paged kernel: {impl} {ticks}")
    ck.require(kernel_compiled or small, "no tpu_custom_call in the lowered "
               "tick: the kernel ran interpreted")
    ck.require(all(len(g) == max_new for g in got), "short replies")
    ck.require(max(gaps) <= TIE_TOL and max(oracle_gaps) <= TIE_TOL,
               "tokens leave the float32 reference by more than a tie: "
               f"engine {gaps}, generate_cached {oracle_gaps}; first index "
               f"differing from generate_cached {first_divergence(got, want)}")

    # int8 KV pages: the engine has no kv_dtype argument, so drive the
    # decoder directly; the quant-error probe is the check
    t0 = time.perf_counter()
    quant = ContinuousDecoder(params, cfg, max_slots=sz["slots"],
                              max_len=sz["max_len"], kv_dtype="int8",
                              quant_probe=1, **sz["engine_kw"])
    reqs = [quant.submit(p, max_new_tokens=max_new) for p in prompts]
    drain(quant, reqs)
    int8_s = time.perf_counter() - t0
    stats = quant._kv.stats
    q_compiled = "tpu_custom_call" in tick_text(quant)
    q_got = [[int(t) for t in r.tokens] for r in reqs]
    quant.stop()
    ck.require(stats["quant_error_probes"] >= 1, "the quant probe never ran")
    ck.require(0.0 < (stats["quant_error_last"] or 0.0) < QUANT_ERR_BOUND
               and stats["quant_error_max"] < QUANT_ERR_BOUND,
               f"int8 quant error {stats['quant_error_last']} "
               f"(max {stats['quant_error_max']})")
    ck.require(quant._attn_impl == "kernel" and (q_compiled or small),
               "int8 decoder did not run the compiled paged kernel")
    ck.require(all(len(g) == max_new for g in q_got), "short int8 outputs")
    t0 = time.perf_counter()
    copies = phase_pool_in_place(sz, small)
    ck.require(small or not any(copies.values()),
               f"programs copy a pool-sized buffer: {copies}")
    return ck, dict(
        pool_copies=copies, pool_copies_s=time.perf_counter() - t0,
        **geometry,
        setup_s=setup_s, run_s=run_s, oracle_s=oracle_s, int8_s=int8_s,
        requests=len(prompts), max_new=max_new,
        prompt_lens=sz["prompt_lens"], slots=sz["slots"], paged_attn=impl,
        attn_ticks=ticks, kernel_compiled=kernel_compiled,
        tokens_equal_oracle=sum(g == w for g, w in zip(got, want)),
        first_divergence=first_divergence(got, want),
        reference_gap_max=max(gaps), oracle_reference_gap_max=max(oracle_gaps),
        int8_kernel_compiled=q_compiled,
        int8_quant_error_last=stats["quant_error_last"],
        int8_quant_error_max=stats["quant_error_max"],
        int8_quant_probes=stats["quant_error_probes"],
        int8_tokens_equal_bf16=sum(g == w for g, w in zip(q_got, got)))


# ---------------------------------------------------------------------------
# C. train


def make_higgs_like(n, seed, f=28):
    """HIGGS-shaped synthetic frame (scripts/bench_gbdt_higgs.py's recipe)."""
    rng = np.random.default_rng(seed)
    y = (rng.random(n) < 0.53).astype(np.float64)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    X[y == 1] += (0.3 * rng.normal(1, 0.2, f)).astype(np.float32)
    return X, y


def higgs_split(sz, seed):
    """((X, y) to fit on, (X, y) held out)."""
    X, y = make_higgs_like(sz["gbdt_rows"] + sz["gbdt_test"], seed)
    n = sz["gbdt_rows"]
    return (X[:n], y[:n]), (X[n:], y[n:])


class HistogramCalls:
    """Counts trace-time calls of the Pallas histogram builder and records
    whether each was asked for interpret mode. A fit whose tree builder is
    already in jit's cache traces nothing, so wrap the first fit too."""

    def __init__(self):
        from mmlspark_tpu.ops import pallas_kernels
        self._mod = pallas_kernels
        self._orig = pallas_kernels.level_histogram_pallas
        self.calls, self.interpreted = 0, 0

    def __enter__(self):
        def counted(*a, **kw):
            self.calls += 1
            self.interpreted += bool(kw.get("interpret", False))
            return self._orig(*a, **kw)
        self._mod.level_histogram_pallas = counted
        return self

    def __exit__(self, *exc):
        self._mod.level_histogram_pallas = self._orig
        return False


@contextlib.contextmanager
def env_var(name, value):
    prev = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


def fit_gbdt(sz, train, test, iterations=None, **estimator_kw):
    """(auc on the held-out frame, model string, fit seconds)."""
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier
    from mmlspark_tpu.train.metrics import roc_auc
    (X, y), (Xt, yt) = train, test
    est = LightGBMClassifier(num_iterations=iterations or sz["gbdt_iters"],
                             max_bin=sz["gbdt_bins"], seed=0, **estimator_kw)
    t0 = time.perf_counter()
    model = est.fit(DataFrame({"features": X, "label": y}))
    fit_s = time.perf_counter() - t0
    out = model.transform(DataFrame({"features": Xt, "label": yt}))
    prob = np.asarray([p[1] for p in out["probability"]], np.float64)
    return roc_auc(yt, prob), model.booster.to_string(), fit_s


def phase_train(sz, seed, small):
    import jax

    from mmlspark_tpu import native
    ck = Checks()
    ck.require(native.available(),
               f"native fast path unavailable: {native.build_error()}")
    train, test = higgs_split(sz, seed)

    # the chip run leaves MMLSPARK_TPU_PALLAS unset (refused otherwise in
    # main); the CPU rehearsal forces the kernel on, in interpret mode
    with env_var("MMLSPARK_TPU_PALLAS", "1" if small else None), \
            HistogramCalls() as calls:
        # set-up: a one-iteration fit compiles the step; train() jits a new
        # closure per call, so the real fit finds it in the persistent cache
        _, _, setup_s = fit_gbdt(sz, train, test, iterations=1)
        auc_pallas, _, fit_s = fit_gbdt(sz, train, test)
    jax.clear_caches()   # the builder choice is read at trace time
    with env_var("MMLSPARK_TPU_PALLAS", "0"), HistogramCalls() as ref_calls:
        auc_ref, _, ref_s = fit_gbdt(sz, train, test)
    ck.require(calls.calls > 0,
               "the Pallas histogram builder was never called")
    ck.require(small or calls.interpreted == 0,
               "the Pallas histogram ran in interpret mode")
    ck.require(ref_calls.calls == 0,
               "the reference fit used the Pallas builder")
    ck.require(0.5 < auc_pallas <= 1.0, f"held-out AUC {auc_pallas}")
    ck.require(abs(auc_pallas - auc_ref) <= AUC_TOL,
               f"AUC {auc_pallas:.5f} (Pallas) vs {auc_ref:.5f} (segment_sum)")
    return ck, dict(setup_s=setup_s, run_s=fit_s, reference_fit_s=ref_s,
                    rows=len(train[0]), features=train[0].shape[1],
                    bins=sz["gbdt_bins"],
                    iterations=sz["gbdt_iters"], held_out=sz["gbdt_test"],
                    auc_pallas=auc_pallas, auc_segment_sum=auc_ref,
                    pallas_histogram_traces=calls.calls,
                    pallas_interpreted=calls.interpreted)


# ---------------------------------------------------------------------------
# --chips 4: D. data-parallel GBDT, E. mesh-mounted decode


def dumped_collectives(dump_dir, module_pattern):
    """Collectives in the optimised HLO XLA dumped for modules matching
    ``module_pattern`` (the compiled text of the program that really ran)."""
    from mmlspark_tpu.parallel.collective_audit import count_collectives
    found = {}
    for path in sorted(glob.glob(os.path.join(
            dump_dir, f"*{module_pattern}*after_optimizations.txt"))):
        with open(path) as fh:
            for kind, row in count_collectives(fh.read()).items():
                found[kind] = found.get(kind, 0) + row["ops"]
    return found


def phase_gbdt_mesh(sz, seed, small, devices, dump_dir):
    from mmlspark_tpu.parallel.mesh import MeshContext
    ck = Checks()
    train, test = higgs_split(sz, seed)
    with env_var("MMLSPARK_TPU_PALLAS", "1" if small else None):
        auc_1, trees_1, fit_1 = fit_gbdt(sz, train, test)
        with MeshContext({"data": 4}, devices=devices), \
                HistogramCalls() as calls:
            auc_4, trees_4, fit_4 = fit_gbdt(
                sz, train, test, parallelism="data_parallel")
    collectives = dumped_collectives(dump_dir, "fused_step")
    ck.require(calls.calls > 0, "the data-parallel fit never built a "
               "histogram with the Pallas kernel")
    ck.require(small or calls.interpreted == 0,
               "the Pallas histogram ran in interpret mode")
    ck.require(collectives.get("all-reduce", 0) > 0,
               "no all-reduce in the compiled data-parallel step: "
               f"{collectives}")
    # the psum adds four partial histograms in another order than one device
    # adds its rows, so near-tied splits may differ; AUC is the criterion
    ck.require(abs(auc_4 - auc_1) <= AUC_TOL,
               f"AUC {auc_4:.5f} on 4 devices vs {auc_1:.5f} on one")
    return ck, dict(setup_s=0.0, run_s=fit_4, one_device_fit_s=fit_1,
                    auc_4_devices=auc_4, auc_1_device=auc_1,
                    same_trees=trees_4 == trees_1, collectives=collectives,
                    pallas_histogram_traces=calls.calls)


def phase_decode_mesh(sz, seed, small, devices):
    from jax.sharding import Mesh

    from mmlspark_tpu.models.zoo.transformer import init_transformer
    from mmlspark_tpu.parallel.collective_audit import count_collectives
    from mmlspark_tpu.parallel.mesh import mesh_shape
    from mmlspark_tpu.serving.continuous import ContinuousDecoder

    ck = Checks()
    cfg, max_new = sz["decoder"], sz["max_new"]
    params = init_transformer(cfg, seed=seed)
    prompts = make_prompts(sz, seed)
    mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "tp"))

    def run(m):
        t0 = time.perf_counter()
        dec = ContinuousDecoder(params, cfg, max_slots=sz["slots"],
                                max_len=sz["max_len"], mesh=m,
                                **sz["engine_kw"])
        reqs = [dec.submit(p, max_new_tokens=max_new) for p in prompts]
        drain(dec, reqs)
        seconds = time.perf_counter() - t0
        toks = [[int(t) for t in r.tokens] for r in reqs]
        text = tick_text(dec, compiled=True)
        impl = dec._attn_impl
        dec.stop()
        return toks, seconds, text, impl

    want, one_s, _, _ = run(None)
    got, mesh_s, text, impl = run(mesh)
    collectives = {k: v["ops"] for k, v in count_collectives(text).items()}
    # the tp all-reduce sums partial products in another order than one
    # device does, so near-tied logits may swap: as in phase B, the float32
    # reference decides whether a differing token is such a tie
    gaps = reference_gaps(params, cfg, prompts, got)
    one_gaps = reference_gaps(params, cfg, prompts, want)
    ck.require(impl == "kernel" and ("tpu_custom_call" in text or small),
               "the mesh decoder did not run the compiled paged kernel")
    ck.require(collectives.get("all-reduce", 0) > 0,
               f"no all-reduce in the compiled mesh tick: {collectives}")
    ck.require(all(len(g) == max_new for g in got), "short outputs")
    ck.require(max(gaps) <= TIE_TOL and max(one_gaps) <= TIE_TOL,
               "tokens leave the float32 reference by more than a tie: "
               f"mesh {gaps}, single device {one_gaps}; first index "
               f"differing {first_divergence(got, want)}")
    return ck, dict(setup_s=0.0, run_s=mesh_s, one_device_s=one_s,
                    mesh=mesh_shape(mesh), requests=len(prompts),
                    max_new=max_new, paged_attn=impl, collectives=collectives,
                    kernel_compiled="tpu_custom_call" in text,
                    tokens_equal_single_device=sum(
                        g == w for g, w in zip(got, want)),
                    first_divergence=first_divergence(got, want),
                    reference_gap_max=max(gaps),
                    single_device_reference_gap_max=max(one_gaps))


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true",
                    help="toy sizes for a CPU rehearsal in interpret mode; "
                         "never a chip result")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the two cross-chip paths (D, E)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", action="append", choices=list("ABCDEFGHIJ"),
                    help="run only this phase (repeatable; for fault-finding)")
    args = ap.parse_args(argv)

    if os.environ.get("MMLSPARK_TPU_FORCE_PLATFORM"):
        refuse("MMLSPARK_TPU_FORCE_PLATFORM is set; the smoke reads the "
               "device JAX really has")
    if not args.small and os.environ.get("MMLSPARK_TPU_PALLAS"):
        refuse("MMLSPARK_TPU_PALLAS is set; the smoke checks the default "
               "builder choice")
    dump_dir = None
    if args.chips == 4:
        # the compiled text of the data-parallel step, as XLA itself dumps it
        dump_dir = tempfile.mkdtemp(prefix="chip_smoke_hlo_")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={dump_dir}"
            " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*fused_step.*"
        ).strip()

    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.small:
        if dev.platform != "tpu":
            refuse(f"needs a TPU; JAX found platform {dev.platform!r} "
                   f"({dev.device_kind}). --small rehearses on the CPU.")
        import bench
        bench.peak_flops(dev.device_kind)   # an unknown kind is an error
    if len(devices) < args.chips:
        refuse(f"--chips {args.chips} needs {args.chips} devices, JAX has "
               f"{len(devices)}")

    from mmlspark_tpu.ops.compile_cache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    if dump_dir:
        # XLA dumps a program's text only when it compiles it
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    events = CacheEvents()
    jax.monitoring.register_event_listener(events)

    from mmlspark_tpu import native
    # read before anything loads the fast path: False means it is built from
    # fastpath.cpp in this run, as in a checkout of what git commits
    native_so_preexisting = os.path.exists(native._SO)
    sz = sizes(args.small)
    if args.chips == 4:
        four = devices[:4]
        phases = {"D": ("D.gbdt_data_parallel", lambda: phase_gbdt_mesh(
                      sz, args.seed, args.small, four, dump_dir)),
                  "E": ("E.decode_mesh", lambda: phase_decode_mesh(
                      sz, args.seed, args.small, four))}
    else:
        phases = {"A": ("A.transform", lambda: phase_transform(
                      sz, args.seed, args.small)),
                  "B": ("B.decode", lambda: phase_decode(
                      sz, args.seed, args.small)),
                  "C": ("C.train", lambda: phase_train(
                      sz, args.seed, args.small)),
                  "F": ("F.hybrid", lambda: phase_hybrid(
                      sz, args.seed, args.small)),
                  "G": ("G.routed", lambda: phase_routed(
                      sz, args.seed, args.small)),
                  "H": ("H.conv_gqa", lambda: phase_conv_gqa(
                      sz, args.seed, args.small)),
                  "I": ("I.latent", lambda: phase_latent(
                      sz, args.seed, args.small)),
                  "J": ("J.state_space", lambda: phase_ssm(
                      sz, args.seed, args.small))}
    for key, (name, run) in phases.items():
        if args.phase and key not in args.phase:
            continue
        hits, misses = events.hits, events.misses
        t0 = time.perf_counter()
        checks, detail = run()
        stats = [d.memory_stats() or {} for d in devices[:args.chips]]
        emit(phase=name, ok=not checks.failed, failed=checks.failed,
             small=args.small,
             platform=dev.platform, device_kind=dev.device_kind,
             wall_s=time.perf_counter() - t0,
             cache_dir=cache_dir, cache_hits=events.hits - hits,
             cache_misses=events.misses - misses,
             native_available=native.available(),
             native_so_preexisting=native_so_preexisting,
             hbm_peak_bytes=[s.get("peak_bytes_in_use") for s in stats],
             hbm_bytes_in_use=[s.get("bytes_in_use") for s in stats],
             **detail)
        if checks.failed:
            raise RuntimeError(f"chip_smoke phase {name} failed: "
                               + "; ".join(checks.failed))
    if dump_dir:
        shutil.rmtree(dump_dir, ignore_errors=True)
    emit(total_s=time.perf_counter() - t_start, cache_dir=cache_dir,
         cache_hits=events.hits, cache_misses=events.misses)
    emit(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
