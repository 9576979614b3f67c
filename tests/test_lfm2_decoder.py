"""The hybrid decoder's gated short-convolution and grouped-query layers under
a routed feed-forward held whole (LFM2-24B-A2B's shape) against their plain
reference, at tiny widths: hidden 64, 4 heads over 2 KV heads of 16, layers
``conv+dense, conv, gqa, conv`` (a dense first layer, then routed), 8 routed
experts of width 32, top-2, every expert held, 3 taps.

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums: logits of scale 0.1-0.6 agree to ~1e-6, and ``TOL`` 5e-5 leaves
room for the online softmax's page-by-page sums against the reference's one
softmax a row. A bfloat16 run of the program misses it by two orders of
magnitude (the last test), so computing in a lower precision than stated
cannot pass.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo.transformer import transformer_apply
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.parallel.moe import MOE_STATS, moe_topk_held
from mmlspark_tpu.serving.continuous import (ContinuousDecoder,
                                             derived_page_size)
from mmlspark_tpu.serving.kv_pool import PagedKVPool
from test_ling_decoder import drain, paged_programs, paged_run

TOL = 5e-5
VOCAB = 97
REFERENCE = bench_run.load_by_path("references", "lfm2_moe")
DRIVER = bench_run.load_by_path("drivers", "generate_lfm2")
F32 = jnp.float32
CONFIG = os.path.join(bench_run.HERE, "configs", "lfm2_24b_a2b_pp5_l9.json")


def tiny_sizes():
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(CONFIG) as fh:
        config = json.load(fh)
    return dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, experts_held=[0, 8], vocab_size=VOCAB,
        layer_types=["conv", "conv", "full_attention", "conv"],
        layers_held=[0, 8, 10, 11], num_hidden_layers=4,
        compute_dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def sizes():
    return tiny_sizes()


@pytest.fixture(scope="module")
def cfg(sizes):
    return DRIVER.program_config(sizes, 256)


@pytest.fixture(scope="module")
def params(sizes):
    return REFERENCE.make_weights(sizes, 5)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, VOCAB, (3, 150)).astype(
        np.int32)


@pytest.fixture(scope="module")
def want(sizes, params, ids):
    """The reference's logits at every position of every sequence."""
    return np.stack([np.asarray(REFERENCE.logits(
        params, sizes, row, np.arange(row.size))) for row in ids])


def program_logits(params, ids, cfg):
    hidden = transformer_apply(params, jnp.asarray(ids), cfg)
    return np.asarray(hidden.astype(F32) @ params["lm_head"]["w"])


def shape_of(sizes):
    return REFERENCE._static(
        {k: sizes[k] for k in REFERENCE.SHAPE_KEYS},
        head_dim=REFERENCE.head_dim(sizes),
        rope_theta=REFERENCE.rope_theta(sizes))


def test_mapping_keeps_the_published_numbers(cfg):
    assert cfg.mixers == ("conv", "conv", "gqa", "conv")
    assert cfg.ffn == ("dense", "moe", "moe", "moe")
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups, r.groups_kept,
            r.scale, r.d_shared) == (8, 0, 8, 2, 1, 1, 1.0, 0)
    assert cfg.conv == (3,) and cfg.norm_eps == 1e-5
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    assert cfg.rope_theta == 1e6


def test_the_real_file_maps_at_its_published_widths():
    with open(CONFIG) as fh:
        config = json.load(fh)
    cfg = DRIVER.program_config(config, 5120)
    hybrid.check_config(cfg)
    assert cfg.mixers == ("conv", "conv", "conv", "gqa", "conv", "conv",
                          "conv", "gqa", "conv")
    assert cfg.ffn == ("dense",) + ("moe",) * 8
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab) == (2048, 32, 8, 64, 11776, 65536)
    assert cfg.routed[:9] == (64, 0, 0, 4, 1, 1, 1.0, 1536, 0)
    assert cfg.routed.held == 64
    # a gqa layer is a dense pool: sixteen pages a slot, held to 16..256
    assert derived_page_size(cfg, 5120) == 256
    assert derived_page_size(cfg, 1024) == 64


def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL


@pytest.mark.parametrize("kind,layer", [("conv", 1), ("gqa", 2)])
def test_a_mixer_alone_matches_the_reference(params, sizes, cfg, kind, layer):
    """One mixer on random rows: the contiguous window from position 0
    against the reference's function of the whole sequence."""
    lp = params["layers"][layer]
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (2, 45, 64)), F32)
    pos = jnp.zeros(2, jnp.int32)
    n = jnp.full(2, 45, jnp.int32)
    wpos = pos[:, None] + jnp.arange(45)
    if kind == "conv":
        got, new = hybrid._conv_layer(lp, x, {"conv": jnp.zeros((2, 2, 64))},
                                      None, hybrid.Window(cfg, pos, n))
        tail = new["conv"]
        ref = REFERENCE.short_conv
        b, _, u = jnp.split(x @ lp["in"]["w"], 3, axis=-1)
        assert np.allclose(tail, (b * u)[:, -2:], atol=1e-6)
    else:
        cache = hybrid.init_hybrid_cache(cfg, 2, 48)[layer]
        got, _ = hybrid._gqa_contiguous(lp, x, cache, wpos,
                                        hybrid.Window(cfg, pos, n))
        ref = REFERENCE.attention
    for b in range(2):
        want = ref(x[b], lp, shape_of(sizes), lambda a: a)
        assert np.abs(np.asarray(got[b] - want)).max() < 1e-5


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 70, 100 and 33 tokens in chunks of 32 (tails carried over
    two and three chunk boundaries, the last chunk padded), then 12 ticks
    through the grouped-query kernel (``kernel``) or the gathered pages
    (``gather``): the reference's logits at every position served."""
    lens = [70, 100, 33]
    firsts, ticks, _, counts = paged_run(params, ids, cfg, impl, lens, 12)
    for b, n in enumerate(lens):
        assert np.abs(firsts[b] - want[b, n - 1]).max() < TOL
        assert np.abs(ticks[b] - want[b, n:n + 12]).max() < TOL
    by = dict(zip(MOE_STATS, counts.sum(axis=0)))
    # 3 rows x 2 experts a token x 3 routed layers x 12 ticks, all held
    assert by["pairs_routed"] == 3 * 2 * 3 * 12 == by["pairs_held"]
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0


def test_a_window_that_ends_mid_chunk_leaves_the_right_tails(params, ids,
                                                             cfg):
    """A chunk of 32 lanes of which 19 are real: the tails are rows 17 and 18
    of ``z``, whatever the padding lanes hold, and the next window continues
    from them as one window of both would."""
    page, per = 8, 8
    window, _ = paged_programs(cfg, "kernel", page, per * page)
    bt = jnp.asarray(1 + np.arange(per)[None], jnp.int32)
    slot = jnp.asarray(0, jnp.int32)

    def run(pool, tokens, off, n):
        tok = np.full((1, 32), 7, np.int32)             # padding: token 7
        tok[0, :n] = tokens
        return window(params, jnp.asarray(tok), jnp.asarray([off], jnp.int32),
                      pool, bt, slot, jnp.asarray([n], jnp.int32))

    pool = hybrid.init_hybrid_pool(cfg, 1 + per, page, 1, per * page)
    _, part = run(pool, ids[0, :19], 0, 19)
    last, both = run(part, ids[0, 19:40], 19, 21)
    pool = hybrid.init_hybrid_pool(cfg, 1 + per, page, 1, per * page)
    _, whole = run(pool, ids[0, :32], 0, 32)
    want_last, whole = run(whole, ids[0, 32:40], 32, 8)
    assert np.abs(np.asarray(last - want_last)).max() < TOL
    for a, b in zip(both, whole):
        if "conv" in a:
            assert np.abs(np.asarray(a["conv"] - b["conv"])).max() < 1e-5
    # and the tails after 19 real lanes are not those after 32
    h = params["embed"]["tok"][jnp.asarray(ids[:1, :19])]
    x = hybrid._rms(h, params["layers"][0]["ln1"], cfg.norm_eps)
    b_, _, u_ = jnp.split(x @ params["layers"][0]["in"]["w"], 3, axis=-1)
    assert np.allclose(part[0]["conv"][0], (b_ * u_)[0, 17:19], atol=1e-6)


def test_a_reused_slot_starts_from_zero_tails(params, cfg, ids):
    page, per = 8, 8
    pool = hybrid.init_hybrid_pool(cfg, 1 + per, page, 1, per * page)
    dirty = [{k: (jnp.full_like(v, 3.0) if k == "conv" else v)
              for k, v in layer.items()} for layer in pool]
    window, _ = paged_programs(cfg, "kernel", page, per * page)
    bt = jnp.asarray(1 + np.arange(per)[None], jnp.int32)
    args = (params, jnp.asarray(ids[:1, :32]), jnp.zeros(1, jnp.int32))
    tail = (bt, jnp.asarray(0, jnp.int32), jnp.asarray([32], jnp.int32))
    clean_logits, clean = window(*args, pool, *tail)
    dirty_logits, after = window(*args, dirty, *tail)
    assert np.array_equal(np.asarray(clean_logits), np.asarray(dirty_logits))
    for a, b in zip(clean, after):
        if "conv" in a:
            assert np.array_equal(np.asarray(a["conv"]), np.asarray(b["conv"]))


# ---- the grouped-query decode call -------------------------------------------

def gqa_case(G, Hkv, dtype=np.float32, seed=0, hd=16, page=8, P=6):
    rng = np.random.default_rng(seed)
    H, B = G * Hkv, 5
    N = B * P + 1
    pos = np.asarray([0, 1, 16, 37, 47], np.int32)
    bt = 1 + rng.permutation(B * P).reshape(B, P).astype(np.int32)
    pool = rng.normal(size=(N, Hkv, page, 2 * hd)).astype(dtype)
    q = rng.normal(size=(B, H, hd)).astype(dtype)
    k, v = (rng.normal(size=(B, Hkv, hd)).astype(dtype) for _ in range(2))
    return q, k, v, pool, bt, pos


@pytest.mark.parametrize("G,Hkv", [(1, 8), (2, 2), (4, 8), (2, 6), (4, 3),
                                   (8, 2)])
def test_grouped_query_call_against_a_float32_oracle(G, Hkv):
    """Query head ``h`` attends KV head ``h // G``'s keys below its row's
    position and the fresh row; pages no row needs hold NaN (a page merely
    visited would poison the block-diagonal zeros); the fresh row lands in
    its page. ``Hkv`` 6 and 3: the last, overlapping group."""
    q, k, v, pool, bt, pos = gqa_case(G, Hkv)
    hd, page = q.shape[-1], pool.shape[2]
    need = np.zeros(len(pool), bool)
    for b in range(len(pos)):
        need[bt[b, :pos[b] // page + 1]] = True
    pool[~need] = np.nan
    ctx, after = pa.paged_attention_gqa(*map(jnp.asarray,
                                             (q, k, v, pool, bt, pos)))
    ctx, after = np.asarray(ctx), np.asarray(after)
    for b in range(len(pos)):
        rows = np.concatenate([pool[p] for p in bt[b]], axis=1)[:, :pos[b]]
        K = np.concatenate([rows[..., :hd], k[b][:, None]], axis=1)
        V = np.concatenate([rows[..., hd:], v[b][:, None]], axis=1)
        for h in range(G * Hkv):
            s = K[h // G] @ q[b, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            assert np.abs(ctx[b, h] - (p / p.sum()) @ V[h // G]).max() < 2e-5
        assert np.array_equal(after[bt[b, pos[b] // page], :, pos[b] % page],
                              np.concatenate([k[b], v[b]], axis=-1))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_at_one_query_a_kv_head_it_is_the_dense_call_bit_for_bit(dtype):
    """``G`` 1: the dense block's fused tick, the same bits (context and
    pool), idle rows included. (That the dense tick itself still returns the
    PARENT commit's bits after the step was generalised was checked tree
    against tree when PR 38 was built: CHANGES.md.)"""
    q, k, v, pool, bt, pos = (jnp.asarray(a) for a in gqa_case(1, 8))
    q, k, v, pool = (a.astype(dtype) for a in (q, k, v, pool))
    active = jnp.asarray([True, True, False, True, True])
    ctx, after = pa.paged_attention_gqa(q, k, v, pool, bt, pos, active=active)
    want, pool_w = pa.paged_attention_window(
        q[:, :, None], k[:, :, None], v[:, :, None], pool, bt, pos,
        active=active)
    assert np.array_equal(np.asarray(ctx.astype(F32)),
                          np.asarray(want[:, :, 0].astype(F32)))
    assert np.array_equal(np.asarray(after.astype(F32)),
                          np.asarray(pool_w.astype(F32)))


def test_a_kv_head_serves_a_power_of_two():
    q, k, v, pool, bt, pos = (jnp.asarray(a) for a in gqa_case(3, 2))
    with pytest.raises(ValueError, match="1, 2, 4"):
        pa.paged_attention_gqa(q, k, v, pool, bt, pos)


# ---- the routed feed-forward held whole --------------------------------------

def test_the_held_layer_is_the_uncut_reference_layer(params, sizes, cfg):
    """``count`` 0: every expert here, so the layer's result is the uncut
    reference's, every pair held."""
    layer = params["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (40, 64)), F32)
    want = REFERENCE.routed_ffn(x, layer, shape_of(sizes))
    y, counts = moe_topk_held(x, x, layer, cfg.routed, jnp.ones(40, bool),
                              interpret=True)
    by = dict(zip(MOE_STATS, np.asarray(counts)))
    assert by["pairs_routed"] == 80 == by["pairs_held"]
    assert by["pairs_dropped"] == 0 == by["pairs_misplaced"]
    assert np.abs(np.asarray(y - want)).max() < 2e-5


def test_no_pair_is_dropped_under_a_one_expert_router(params, sizes, cfg):
    """A selection bias that puts expert 3 first for every token: 100 pairs
    on one expert (7 tiles), none dropped, padding routes nowhere."""
    layer = dict(params["layers"][1]["moe"],
                 bias=jnp.zeros(8).at[3].set(10.0))
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (128, 64)), F32)
    valid = jnp.arange(128) < 100
    y, counts = moe_topk_held(x, x, layer, cfg.routed, valid, interpret=True)
    by = dict(zip(MOE_STATS, np.asarray(counts)))
    assert by["pairs_routed"] == 200 == by["pairs_held"]
    assert by["expert_load_max"] == 100
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0
    want = REFERENCE.routed_ffn(x, layer, shape_of(sizes))
    assert np.abs(np.asarray(y - want)[:100]).max() < 2e-5


@pytest.mark.parametrize("rows,folds", [(4, False), (4 + 64, True)],
                         ids=["plain_tick", "carrying_step"])
def test_a_product_folds_an_experts_run_of_tiles(params, sizes, cfg, rows,
                                                 folds):
    """``tiles`` and ``product_steps`` of the layer's counts: a tick's few
    rows put one tile on an expert, a product a tile; a step that carries a
    64-lane window puts ~17 pairs on each of the 8 experts, two tiles that
    ONE product multiplies, and the layer's result is still the uncut
    reference's."""
    layer = params["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(7).normal(0, 1, (rows, 64)), F32)
    y, counts = moe_topk_held(x, x, layer, cfg.routed, jnp.ones(rows, bool),
                              interpret=True)
    by = dict(zip(MOE_STATS, np.asarray(counts)))
    assert by["pairs_dropped"] == 0 == by["pairs_misplaced"]
    assert by["product_steps"] == by["experts_touched"]
    if folds:
        assert by["tiles"] > by["product_steps"] == 8
    else:
        assert by["tiles"] == by["product_steps"]
    want = REFERENCE.routed_ffn(x, layer, shape_of(sizes))
    assert np.abs(np.asarray(y - want)).max() < 2e-5


# ---- the engine --------------------------------------------------------------

def greedy(params, sizes, prompt, n):
    """The reference's greedy continuation, a full forward a token."""
    seq = list(prompt)
    for _ in range(n):
        row = np.asarray(REFERENCE.logits(params, sizes, np.asarray(seq),
                                          [len(seq) - 1]))[0]
        seq.append(int(row.argmax()))
    return seq[len(prompt):]


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_decoder_equals_the_reference_with_slots_reused(params, cfg, sizes,
                                                        ids, impl):
    """Five requests through three slots: a reused slot starts from zero
    tails (else its tokens would differ), the ticks are labelled by path and
    the prompt tokens are counted."""
    decoder = ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                                page_size=8, prefill_chunk=32,
                                paged_attn=impl)
    prompts = [ids[0, :40], ids[1, :71], ids[2, :9], ids[0, 50:120],
               ids[1, 30:63]]
    got = drain(decoder, [decoder.submit(p, 6) for p in prompts])
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, sizes, p, 6)
    stats = decoder._kv.stats
    gqa = "attn_ticks_gqa" + ("" if impl == "kernel" else "_window")
    assert stats["attn_ticks_conv"] == stats[gqa] \
        == stats["attn_ticks_" + impl] - stats["prefill_chunks"] > 0
    assert ("attn_ticks_gqa_window" in stats) == (impl == "gather")
    assert stats["prefill_tokens"] == sum(len(p) for p in prompts)
    assert stats["moe_pairs_dropped"] == 0 == stats["moe_pairs_misplaced"]
    assert stats["moe_pairs_held"] == stats["moe_pairs_routed"] > 0


def test_prefix_hit_restores_tails_and_pages(params, cfg, sizes, ids):
    """A registered prefix of 48 tokens (a snapshot of every conv layer's
    tails beside the gqa layer's pages), then a hit: the same tokens as the
    whole prefill, which the reference decides."""
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=224,
                            page_size=8, prefill_chunk=32)
    doc = ids[2, :48]
    first = np.concatenate([doc, ids[0, :11]])
    second = np.concatenate([doc, ids[1, :23]])
    a = drain(dec, [dec.submit(first, 5, prefix_key="d", prefix_len=48)])[0]
    b = drain(dec, [dec.submit(second, 5, prefix_key="d", prefix_len=48)])[0]
    assert list(a) == greedy(params, sizes, first, 5)
    assert list(b) == greedy(params, sizes, second, 5)
    stats = dec._kv.stats
    assert stats["state_snapshots_stored"] == 1 == \
        stats["state_snapshots_restored"]
    # three conv layers: 2 rows of 64 float32 values each; no state, no ck
    assert stats["state_snapshot_bytes_stored"] == 3 * 2 * 64 * 4
    assert dec.stats["prefix_hits"] == 1


def test_defragmentation_keeps_tails_and_moves_pages(params, cfg, sizes, ids):
    """Long requests retire under a short one; compaction permutes the gqa
    layer's pages and leaves every slot row where it is: the survivor's
    tokens are still the reference's."""
    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                            page_size=8, prefill_chunk=32, kv_pages=40,
                            defrag_threshold=1)
    reqs = [dec.submit(ids[0, :90], 2), dec.submit(ids[1, :90], 2),
            dec.submit(ids[2, :20], 30)]
    got = drain(dec, reqs)
    assert list(got[2]) == greedy(params, sizes, ids[2, :20], 30)
    assert dec._kv.stats["defrag_moves"] > 0


def test_pool_shapes_come_from_the_mixers(cfg):
    pool = PagedKVPool(cfg, num_pages=9, page_size=8, residency=False,
                       slots=2, slot_positions=64)
    assert [sorted(layer) for layer in pool.buffers] == [
        ["conv"], ["conv"], ["kv"], ["conv"]]
    assert pool.buffers[2]["kv"].shape == (9, 2, 8, 32)
    assert pool.buffers[0]["conv"].shape == (2, 2, 64)
    assert pool.bytes_per_position() == 2 * 2 * 16 * 4


@pytest.mark.parametrize("kwargs,reason", [
    (dict(kv_dtype="int8"), "grouped-query kernel"),
    (dict(draft_params={}, draft_cfg=None), "conv layer's"),
    (dict(mesh="a mesh"), "a conv layer's tails"),
])
def test_refused_combinations_say_why(params, cfg, kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        ContinuousDecoder(params, cfg, max_slots=2, max_len=64, **kwargs)


@pytest.mark.parametrize("change,message", [
    (lambda c: c._replace(mixers=("conv", "mamba", "gqa", "conv")),
     "unknown mixer.*lightning \\| sparse \\| kda \\| mla \\| conv \\| gqa"),
    (lambda c: c._replace(conv=None), "cfg.conv"),
    (lambda c: c._replace(conv=c.conv._replace(taps=1)), "cfg.conv"),
    (lambda c: c._replace(heads=6, kv_heads=2, head_dim=16), "gqa layers"),
    (lambda c: c._replace(routed=None), "cfg.routed"),
])
def test_config_is_checked(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        hybrid.check_config(change(cfg))


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    low = cfg._replace(dtype=jnp.bfloat16)
    got = program_logits(params, ids[:1], low)
    assert np.abs(got - want[:1]).max() > 20 * TOL
