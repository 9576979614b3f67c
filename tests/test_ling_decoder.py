"""The hybrid decoder's routed, delta-rule and latent layers (Ling-3.0-flash's
shape) against their plain reference, at tiny widths: hidden 64, 4 heads of
16 (latent 32, rotary 8), layers ``kda+dense, mla, kda, kda, mla`` (published
indices 0, 2-5 under a group size of 3), 32 routed experts of width 32 in 4
groups, 2 groups kept, top-4, one shared expert, experts 0-7 held: a quarter,
as the benchmark's share is (a share under 8 experts is refused, so the tiny
router is 32 wide, not 16).

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums: logits of scale 0.1-0.6 agree to ~1e-6, and ``TOL`` 5e-5 leaves
room for the chunked delta rule's 64-token products against the reference's
token-by-token recurrence. A bfloat16 run of the program misses it by two
orders of magnitude (the last test), so computing in a lower precision than
stated cannot pass.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo.transformer import (
    decode_step_paged, decode_window_paged, transformer_apply)
from mmlspark_tpu.ops.kda_attention import kda_decode_step
from mmlspark_tpu.parallel.moe import MOE_STATS, moe_topk_held
from mmlspark_tpu.serving.continuous import ContinuousDecoder
from mmlspark_tpu.serving.kv_pool import PagedKVPool

TOL = 5e-5
VOCAB = 97
REFERENCE = bench_run.load_by_path("references", "ling_flash")
DRIVER = bench_run.load_by_path("drivers", "generate_ling")
F32 = jnp.float32


def tiny_sizes():
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(os.path.join(bench_run.HERE, "configs",
                           "ling3_flash_ep4_l7.json")) as fh:
        config = json.load(fh)
    return dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        head_dim=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, n_group=4, topk_group=2,
        num_experts_per_tok=4, num_experts=8, experts_held=[0, 8],
        published=dict(config["published"], num_experts=32),
        vocab_size=VOCAB, layer_group_size=3, layers_held=[0, 2, 3, 4, 5],
        num_hidden_layers=5, compute_dtype="float32", param_dtype="float32")


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


def test_mapping_keeps_the_published_numbers(sizes, cfg):
    assert cfg.mixers == ("kda", "mla", "kda", "kda", "mla")
    assert cfg.ffn == ("dense", "moe", "moe", "moe", "moe")
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups,
            r.groups_kept, r.scale) == (32, 0, 8, 4, 4, 2, 2.5)
    assert cfg.latent == (32, 16, 8, 16, 0, True) and cfg.kda == (4, -5.0)
    assert r.swiglu_limits == (0,) * 8


def test_the_real_file_maps_at_its_published_widths():
    with open(os.path.join(bench_run.HERE, "configs",
                           "ling3_flash_ep4_l7.json")) as fh:
        config = json.load(fh)
    cfg = DRIVER.program_config(config, 4096)
    hybrid.check_config(cfg)
    assert cfg.mixers == ("kda",) * 6 + ("mla",)
    assert cfg.ffn == ("dense",) + ("moe",) * 6
    assert (cfg.d_model, cfg.heads, cfg.head_dim, cfg.d_ff, cfg.vocab) == (
        2560, 32, 128, 6144, 39296)
    assert cfg.routed[:9] == (512, 0, 128, 8, 8, 4, 2.5, 768, 768)
    assert cfg.latent == (512, 128, 64, 128, 0, True)


def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL


@functools.lru_cache(maxsize=None)
def paged_programs(cfg, impl, page, length):
    """The chunk window and the decode tick, jitted once a configuration."""
    def window(params, tok, off, bufs, bt_row, slot, n):
        return decode_window_paged(
            params, tok, off, bufs, bt_row, cfg, page_size=page,
            length=length, impl=impl, n_valid=n, slot=slot, last_only=True)

    def tick(params, tok, pos, bufs, bt, active):
        counted = {}
        logits, bufs = decode_step_paged(
            params, tok, pos, bufs, bt, cfg, page_size=page, length=length,
            active=active, impl=impl, stats=counted)
        return logits, bufs, counted["moe"]

    return jax.jit(window), jax.jit(tick)


def paged_run(params, ids, cfg, impl, prompt_lens, steps, chunk=32, page=8):
    """Prefill each row's prompt in chunks of ``chunk`` through the pool,
    then ``steps`` decode ticks teacher-forced on ``ids``. Returns the
    logits of each prompt's last position and of every tick, the pool and
    the ticks' routing counts."""
    B, L = ids.shape
    per = -(-L // page)
    pool = hybrid.init_hybrid_pool(cfg, 1 + B * per, page, B, per * page)
    bt = jnp.asarray(1 + np.arange(B * per).reshape(B, per), jnp.int32)
    window, tick = paged_programs(cfg, impl, page, per * page)
    firsts = []
    for b, n in enumerate(prompt_lens):
        for off in range(0, n, chunk):
            w = min(chunk, n - off)
            tok = np.zeros((1, chunk), np.int32)
            tok[0, :w] = ids[b, off:off + w]
            last, pool = window(params, jnp.asarray(tok),
                                jnp.asarray([off], jnp.int32), pool,
                                bt[b:b + 1], jnp.asarray(b, jnp.int32),
                                jnp.asarray([w], jnp.int32))
        firsts.append(np.asarray(last[0]))
    pos = np.asarray(prompt_lens)
    ticks, counts = [], []
    for s in range(steps):
        tok = jnp.asarray(ids[np.arange(B), pos + s])
        logits, pool, moe = tick(params, tok, jnp.asarray(pos + s, jnp.int32),
                                 pool, bt, jnp.ones(B, bool))
        ticks.append(np.asarray(logits))
        counts.append(np.asarray(moe))
    return np.stack(firsts), np.stack(ticks, axis=1), pool, np.stack(counts)


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 70, 100 and 33 tokens in chunks of 32 (a chunk boundary, a
    delta-rule chunk boundary inside none of them: the 64-token form is
    tested below), then 12 ticks through the kda step and the absorbed
    latent kernel (``kernel``) or the chunked and expanded forms
    (``gather``): the reference's logits at every position served."""
    lens = [70, 100, 33]
    firsts, ticks, _, counts = paged_run(params, ids, cfg, impl, lens, 12)
    for b, n in enumerate(lens):
        assert np.abs(firsts[b] - want[b, n - 1]).max() < TOL
        assert np.abs(ticks[b] - want[b, n:n + 12]).max() < TOL
    by = dict(zip(MOE_STATS, counts.sum(axis=0)))
    # 3 rows x 4 experts a token x 4 routed layers x 12 ticks
    assert by["pairs_routed"] == 3 * 4 * 4 * 12
    assert 0 < by["pairs_held"] < by["pairs_routed"]
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0


def test_absorbed_latent_attention_is_the_expanded_one(params, ids, cfg):
    """The tick's absorbed kernel against the same tick attending expanded
    (``gather``), from the same prefilled pool."""
    _, kern, _, _ = paged_run(params, ids, cfg, "kernel", [90, 64, 17], 6)
    _, gath, _, _ = paged_run(params, ids, cfg, "gather", [90, 64, 17], 6)
    assert np.abs(kern - gath).max() < TOL


def recurrence(q, k, v, g, beta, state):
    """The delta rule token by token: (B, H, W, d) inputs, float64."""
    q, k, v, g, beta, S = (np.asarray(t, np.float64)
                           for t in (q, k, v, g, beta, state))
    out = np.zeros_like(v)
    for t in range(q.shape[2]):
        S = np.exp(g[:, :, t])[..., None] * S
        u = v[:, :, t] - np.einsum("bhd,bhde->bhe", k[:, :, t], S)
        S = S + np.einsum("bhd,bhe->bhde", beta[:, :, t, None] * k[:, :, t], u)
        out[:, :, t] = np.einsum("bhd,bhde->bhe", q[:, :, t], S)
    return out, S


def delta_inputs(W, seed=0, B=2, H=3, d=16):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(0, 1, (B, H, W, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * rng.uniform(0, 1, (B, H, W, d)).astype(np.float32)
    beta = rng.uniform(0, 1, (B, H, W)).astype(np.float32)
    state = rng.normal(0, 1, (B, H, d, d)).astype(np.float32)
    return q, k, v, g, beta, state


@pytest.mark.parametrize("W", [1, 8, 64, 100, 192])
def test_chunked_delta_rule_is_the_recurrence(W):
    """Across chunk boundaries (100 = 64 + 36 padded, 192 = three chunks),
    with decays down to exp(-5) a token (exp(-320) a chunk: a ratio of
    cumulated powers would overflow)."""
    args = delta_inputs(W)
    o, S = hybrid.kda_chunk(*map(jnp.asarray, args))
    want_o, want_S = recurrence(*args)
    assert np.abs(np.asarray(o) - want_o).max() < 2e-5
    assert np.abs(np.asarray(S) - want_S).max() < 2e-5


def test_chunked_delta_rule_in_two_windows_is_one():
    """A window of 100 then one of 60 equals one of 160: the state carries
    everything across a window's end."""
    q, k, v, g, beta, state = map(jnp.asarray, delta_inputs(160, seed=3))
    o, S = hybrid.kda_chunk(q, k, v, g, beta, state)
    a = [t[:, :, :100] for t in (q, k, v, g, beta)]
    b = [t[:, :, 100:] for t in (q, k, v, g, beta)]
    o1, S1 = hybrid.kda_chunk(*a, state)
    o2, S2 = hybrid.kda_chunk(*b, S1)
    assert np.abs(np.asarray(jnp.concatenate([o1, o2], axis=2) - o)).max() \
        < 2e-5
    assert np.abs(np.asarray(S2 - S)).max() < 2e-5


def test_kda_decode_kernel_is_the_recurrence():
    q, k, v, g, beta, state = delta_inputs(1, seed=1, B=3, H=8)
    active = np.array([True, False, True])
    o, S = kda_decode_step(*(jnp.asarray(t[:, :, 0]) for t in (q, k, v)),
                           jnp.exp(jnp.asarray(g[:, :, 0])),
                           jnp.asarray(beta[:, :, 0]), jnp.asarray(state),
                           jnp.asarray(active), interpret=True)
    want_o, want_S = recurrence(q, k, v, g, beta, state)
    assert np.abs(np.asarray(o)[active] - want_o[active, :, 0]).max() < 1e-5
    assert np.abs(np.asarray(S)[active] - want_S[active]).max() < 1e-5
    assert np.array_equal(np.asarray(S)[1], state[1])   # idle: untouched


# ---- the routed feed-forward -------------------------------------------------

@pytest.fixture(scope="module")
def uncut(sizes):
    """The routed layer whole: all 32 experts held."""
    whole = dict(sizes, num_experts=32, experts_held=[0, 32],
                 layers_held=[0, 2], num_hidden_layers=2)
    layer = REFERENCE.make_weights(whole, 7)["layers"][1]["moe"]
    return whole, layer


def test_the_shares_add_up_to_the_uncut_layer(sizes, cfg, uncut):
    """Four shares of 8 experts: their routed parts and the shared expert
    ONCE are the uncut reference's layer."""
    whole, layer = uncut
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (40, 64)), F32)
    want = REFERENCE.routed_ffn(x, layer, REFERENCE._static(
        {k: whole[k] for k in REFERENCE.SHAPE_KEYS}))
    valid = jnp.ones(40, bool)
    total, held = 0.0, 0
    for j in range(4):
        share = cfg.routed._replace(first=8 * j, count=8)
        p = {"router": layer["router"], "bias": layer["bias"],
             "experts": {k: v[8 * j:8 * j + 8]
                         for k, v in layer["experts"].items()}}
        y, counts = moe_topk_held(x, x, p, share, valid, interpret=True)
        total = total + y
        held += int(counts[1])
        assert int(counts[0]) == 40 * 4 and int(counts[2]) == 0
    assert held == 40 * 4           # every pair lands on exactly one share
    first = cfg.routed._replace(first=0, count=8)
    p = {"router": layer["router"], "bias": layer["bias"],
         "experts": {k: v[:8] for k, v in layer["experts"].items()}}
    only, _ = moe_topk_held(x, x, p, first, valid, interpret=True)
    with_shared, _ = moe_topk_held(x, x, dict(p, shared=layer["shared"]),
                                   first, valid, interpret=True)
    total = total + (with_shared - only)
    assert np.abs(np.asarray(total - want)).max() < 2e-5


def test_a_share_is_the_references_share(sizes, cfg, uncut):
    """One share against the reference GIVEN the same share."""
    whole, layer = uncut
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (24, 64)), F32)
    mine = dict(whole, experts_held=[8, 16])
    p = {"router": layer["router"], "bias": layer["bias"],
         "shared": layer["shared"],
         "experts": {k: v[8:16] for k, v in layer["experts"].items()}}
    want = REFERENCE.routed_ffn(x, p, REFERENCE._static(
        {k: mine[k] for k in REFERENCE.SHAPE_KEYS}))
    y, _ = moe_topk_held(x, x, p, cfg.routed._replace(first=8, count=8),
                         jnp.ones(24, bool), interpret=True)
    assert np.abs(np.asarray(y - want)).max() < 2e-5


def test_no_pair_is_dropped_when_every_token_takes_one_expert(cfg, uncut):
    """A selection bias that puts expert 3 first for every token: 100 pairs
    on one expert (7 tiles), none dropped, padding routes nowhere, and the
    result is still the reference's."""
    whole, layer = uncut
    bias = jnp.zeros(32).at[3].set(10.0)
    p = {"router": layer["router"], "bias": bias, "shared": layer["shared"],
         "experts": {k: v[:8] for k, v in layer["experts"].items()}}
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (128, 64)), F32)
    valid = jnp.arange(128) < 100
    y, counts = moe_topk_held(x, x, p, cfg.routed, valid, interpret=True)
    by = dict(zip(MOE_STATS, np.asarray(counts)))
    assert by["pairs_routed"] == 400 and by["expert_load_max"] == 100
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0
    want = REFERENCE.routed_ffn(x, p, REFERENCE._static(
        {k: dict(whole, experts_held=[0, 8])[k]
         for k in REFERENCE.SHAPE_KEYS}))
    assert np.abs(np.asarray(y - want)[:100]).max() < 2e-5


# ---- the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def decoder(params, cfg):
    return ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                             page_size=8, prefill_chunk=32)


def drain(decoder, reqs):
    while not all(r.done for r in reqs):
        decoder.step()
    return [decoder.result(r) for r in reqs]


def greedy(params, sizes, prompt, n):
    """The reference's greedy continuation, a full forward a token."""
    seq = list(prompt)
    for _ in range(n):
        row = np.asarray(REFERENCE.logits(params, sizes, np.asarray(seq),
                                          [len(seq) - 1]))[0]
        seq.append(int(row.argmax()))
    return seq[len(prompt):]


def test_decoder_equals_the_reference_with_slots_reused(decoder, params,
                                                        sizes, ids):
    """Five requests through three slots: a reused slot starts from a zero
    state and zero convolution tails (else its tokens would differ), and
    the routing counts ride out with the tokens."""
    prompts = [ids[0, :40], ids[1, :71], ids[2, :9], ids[0, 50:120],
               ids[1, 30:63]]
    reqs = [decoder.submit(p, 6) for p in prompts]
    got = drain(decoder, reqs)
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, sizes, p, 6)
    stats = decoder._kv.stats
    assert stats["attn_ticks_kda"] == stats["attn_ticks_latent"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0
    assert stats["moe_pairs_dropped"] == 0 == stats["moe_pairs_misplaced"]
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]
    assert stats["moe_experts_touched"] > 0 < stats["moe_expert_load_max"]


def test_a_reused_slot_starts_from_zero(params, cfg, ids):
    """The rows a slot's first window sees are zeroed whatever the last
    request left there."""
    page, per = 8, 8
    pool = hybrid.init_hybrid_pool(cfg, 1 + per, page, 1, per * page)
    dirty = [{k: (jnp.full_like(v, 3.0) if k in ("state", "conv") else v)
              for k, v in layer.items()} for layer in pool]
    window, _ = paged_programs(cfg, "kernel", page, per * page)
    bt = jnp.asarray(1 + np.arange(per)[None], jnp.int32)
    args = (params, jnp.asarray(ids[:1, :32]), jnp.zeros(1, jnp.int32))
    tail = (bt, jnp.asarray(0, jnp.int32), jnp.asarray([32], jnp.int32))
    clean_logits, clean = window(*args, pool, *tail)
    dirty_logits, after = window(*args, dirty, *tail)
    assert np.array_equal(np.asarray(clean_logits), np.asarray(dirty_logits))
    for a, b in zip(clean, after):
        for k in a:
            if k in ("state", "conv"):
                assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_prefix_hit_restores_state_and_tails(params, cfg, sizes, ids):
    """A registered prefix of 48 tokens (a snapshot of every kda layer's
    state AND convolution tails beside the latent pages), then a hit: the
    same tokens as the whole prefill, which the reference decides."""
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
    # three kda layers: a (4, 16, 16) float32 state and 3 x 192 tails each
    assert stats["state_snapshot_bytes_stored"] == 3 * (4 * 16 * 16 * 4
                                                        + 3 * 192 * 4)
    assert dec.stats["prefix_hits"] == 1


def test_pool_shapes_come_from_the_mixers(cfg):
    pool = PagedKVPool(cfg, num_pages=9, page_size=8, residency=False,
                       slots=2, slot_positions=64)
    kinds = [sorted(layer) for layer in pool.buffers]
    assert kinds == [["conv", "state"], ["kv"], ["conv", "state"],
                     ["conv", "state"], ["kv"]]
    assert pool.buffers[1]["kv"].shape == (9, 1, 8, 128)    # whole registers
    assert pool.buffers[0]["conv"].shape == (2, 3, 192)
    assert pool.buffers[0]["state"].shape == (2, 4, 16, 16)
    assert pool.bytes_per_position() == 2 * 128 * 4


@pytest.mark.parametrize("kwargs,reason", [
    (dict(kv_dtype="int8"), "latent pages"),
    (dict(draft_params={}, draft_cfg=None), "convolution tails"),
])
def test_refused_combinations_say_why(params, cfg, kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        ContinuousDecoder(params, cfg, max_slots=2, max_len=64, **kwargs)


@pytest.mark.parametrize("change,message", [
    (lambda c: c._replace(routed=c.routed._replace(count=4)),
     "at least 8"),
    (lambda c: c._replace(routed=c.routed._replace(
        swiglu_limits=(0, 0, 4, 0))), "clamp"),
    (lambda c: c._replace(routed=c.routed._replace(first=28)),
     "experts held"),
    (lambda c: c._replace(ffn=("dense", "moe")), "a layer"),
    (lambda c: c._replace(latent=None), "cfg.latent"),
    (lambda c: c._replace(kda=None), "cfg.kda"),
    (lambda c: c._replace(mixers=("kda", "mamba", "kda", "kda", "mla")),
     "unknown mixer"),
    (lambda c: c._replace(routed=None), "cfg.routed"),
])
def test_config_is_checked(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        hybrid.check_config(change(cfg))


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    low = cfg._replace(dtype=jnp.bfloat16)
    got = program_logits(params, ids[:1], low)
    assert np.abs(got - want[:1]).max() > 20 * TOL
