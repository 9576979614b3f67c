"""The hybrid decoder at Granite 4.0-H-Small's shape against its plain
reference, at tiny widths: hidden 64, 8 heads over 2 KV heads of 8 without
positions under an attention scale of 1/8 (1 / head size, not its root),
Mamba-2 of 16 heads of 8 on a state 16 wide in ONE group, 4 taps, chunks of
8; EVERY layer a routed feed-forward: a bias-free router 16 wide of which
experts 0-7 are held, the 3 largest logits a token, a softmax over the chosen,
SwiGLU experts 24 wide beside a shared expert 48 wide; muP's multipliers 12,
0.22, 1/16; the head is the token table. Layers ``mamba, attention, mamba``.

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums. The logits are the token table's 0.0025 times a normed row over
``logits_scaling`` 16: scale 0.00125, agreeing to ~4e-9; ``TOL`` 4e-8 leaves
ten times that. A bfloat16 run of the program misses it by two orders of magnitude
(the last test), so computing in a lower precision than stated cannot pass.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo.transformer import (head, init_transformer,
                                                 transformer_apply)
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.ops.ssm_step import (pack_state, pairs_a_step,
                                       ssm_decode_step, unpack_state)
from mmlspark_tpu.parallel.moe import MOE_STATS, moe_topk_held, route_topk
from mmlspark_tpu.serving.continuous import (ContinuousDecoder,
                                             _state_programs,
                                             derived_page_size)
from test_ling_decoder import drain, paged_programs, paged_run

TOL = 4e-8
VOCAB = 97
REFERENCE = bench_run.load_by_path("references", "granitemoehybrid")
DRIVER = bench_run.load_by_path("drivers", "generate_granite")
F32 = jnp.float32
CONFIG = os.path.join(bench_run.HERE, "configs",
                      "granite4_h_small_ep2_l10.json")


def tiny_sizes(**changes):
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(CONFIG) as fh:
        config = json.load(fh)
    return dict(dict(
        config, hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, attention_multiplier=0.125, mamba_n_heads=16,
        mamba_d_head=8, mamba_d_state=16, mamba_n_groups=1,
        mamba_chunk_size=8, intermediate_size=24, shared_intermediate_size=48,
        num_local_experts=8, experts_held=[0, 8],
        published=dict(config["published"], num_local_experts=16),
        num_experts_per_tok=3, vocab_size=VOCAB,
        layer_types=["mamba", "full_attention", "mamba"],
        layers_held=[0, 1, 2], num_hidden_layers=3,
        compute_dtype="float32", param_dtype="float32"), **changes)


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


def reference_logits(params, sizes, row):
    return np.asarray(REFERENCE.logits(params, sizes, row,
                                       np.arange(len(row))))


@pytest.fixture(scope="module")
def want(sizes, params, ids):
    """The reference's logits at every position of every sequence."""
    return np.stack([reference_logits(params, sizes, row) for row in ids])


def program_logits(params, ids, cfg):
    return np.asarray(head(params, transformer_apply(params, jnp.asarray(ids),
                                                     cfg)))


# ---- the mapping --------------------------------------------------------------

def test_mapping_keeps_the_published_numbers(cfg):
    assert cfg.mixers == ("ssm", "gqa", "ssm") and cfg.ffn == ("moe",) * 3
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups, r.groups_kept,
            r.scale, r.d_expert, r.d_shared, r.latent, r.form, r.score) == (
                16, 0, 8, 3, 1, 1, 1.0, 24, 48, 0, "swiglu", "softmax")
    assert cfg.ssm == (16, 8, 16, 1, 4, 8)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (8, 2, 8)
    assert (cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
            cfg.logit_scale) == (0.125, 12.0, 0.22, 1 / 16)
    assert cfg.norm_eps == 1e-5 and cfg.qk_positions is False
    assert cfg.tied_head is True


def test_the_real_file_maps_at_its_published_widths():
    with open(CONFIG) as fh:
        config = json.load(fh)
    cfg = DRIVER.program_config(config, 65536)
    hybrid.check_config(cfg)
    assert cfg.mixers == ("ssm",) * 5 + ("gqa",) + ("ssm",) * 4
    assert cfg.ffn == ("moe",) * 10
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim,
            cfg.vocab) == (4096, 32, 8, 128, 50176)
    assert cfg.ssm == (128, 64, 128, 1, 4, 256)
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.d_expert, r.d_shared,
            r.score) == (72, 0, 36, 10, 768, 1536, "softmax")
    assert cfg.attn_scale == 1 / 128 and cfg.tied_head
    assert derived_page_size(cfg, 65536) == 256
    shapes = hybrid.pool_shapes(cfg, 1400, 256, 32, 65536)
    # a page of the one gqa layer: 8 KV heads x 256 x 2 x 128 bf16 = 1 MiB
    assert shapes[5] == {"kv": ((1400, 8, 256, 256), jnp.bfloat16)}
    # a state: 128 heads of 64 x 128 float32 = 4.19 MB, held in pairs
    assert shapes[0]["state"] == ((32, 64, 128, 128), F32)
    assert shapes[0]["conv"] == ((32, 3, 8448), jnp.bfloat16)
    # one group of 64 pairs of 64 KiB: 32 pairs a grid step, 2 steps a row
    assert pairs_a_step(1, 128 * 128 * 4, 64) == (1, 32)
    with pytest.raises(ValueError, match="disagree"):
        DRIVER.program_config(dict(config, experts_held=[0, 72]), 65536)
    with pytest.raises(ValueError, match="published form"):
        DRIVER.program_config(dict(config, position_embedding_type="rope"),
                              65536)


# ---- against the reference: logits --------------------------------------------

def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL
    assert want.std() > 1e-3


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 70, 100 and 33 tokens in windows of 32 (state and tails
    carried over window boundaries, four scan chunks of 8 a window, the last
    window padded), then 12 ticks through the state-space step at one group
    and the grouped-query kernel under the model's scale (``kernel``) or the
    chunked scan and the gathered pages (``gather``): the reference's logits
    at every position served."""
    lens = [70, 100, 33]
    firsts, ticks, _, counts = paged_run(params, ids, cfg, impl, lens, 12)
    for b, n in enumerate(lens):
        assert np.abs(firsts[b] - want[b, n - 1]).max() < TOL
        assert np.abs(ticks[b] - want[b, n:n + 12]).max() < TOL
    by = dict(zip(MOE_STATS, counts.sum(axis=0)))
    # 3 rows x 3 experts a token x 3 routed layers x 12 ticks over 16 experts
    assert by["pairs_routed"] == 3 * 3 * 3 * 12
    assert 0 < by["pairs_held"] < by["pairs_routed"]
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0


def test_a_stored_context_serves_two_callers(params, sizes, cfg, ids):
    """A 64-token context prefilled once into slot 0; its rows a slot (two
    ssm layers' state and tails) copied out by the engine's snapshot program
    and restored into slots 1 and 2, whose block tables name the context's
    eight pages (shared, never written) before their own; two different
    questions, then ten ticks of both rows at once: the reference's logits on
    ``context + question + continuation`` for each caller."""
    page, per, plen = 8, 16, 64
    window, tick = paged_programs(cfg, "kernel", page, per * page)
    snapshot, restore = _state_programs(False)
    doc = ids[0, :plen]
    rows = [np.concatenate([doc, ids[1, :21 + 10]]),
            np.concatenate([doc, ids[2, :13 + 10]])]
    asked = [plen + 21, plen + 13]
    want = [reference_logits(params, sizes, r) for r in rows]
    shared = 1 + np.arange(plen // page)
    bt = np.zeros((3, per), np.int32)
    bt[0, :8] = shared
    for b in (1, 2):
        bt[b, :8] = shared
        bt[b, 8:] = 9 + 8 * (b - 1) + np.arange(8)
    bt = jnp.asarray(bt)
    pool = hybrid.init_hybrid_pool(cfg, 1 + 8 + 16, page, 3, per * page)

    def prefill(pool, b, tokens, off):
        last = None
        for lo in range(0, len(tokens), 32):
            w = min(32, len(tokens) - lo)
            tok = np.zeros((1, 32), np.int32)
            tok[0, :w] = tokens[lo:lo + w]
            last, pool = window(params, jnp.asarray(tok),
                                jnp.asarray([off + lo], jnp.int32), pool,
                                bt[b:b + 1], jnp.asarray(b, jnp.int32),
                                jnp.asarray([w], jnp.int32))
        return np.asarray(last[0]), pool

    _, pool = prefill(pool, 0, doc, 0)
    stored = [np.asarray(pool[1]["kv"][p]) for p in shared]
    snap = snapshot(pool, jnp.asarray(0, jnp.int32))
    assert [sorted(s) for s in snap] == [["conv", "state"], [],
                                         ["conv", "state"]]
    for b in (1, 2):
        pool = restore(pool, snap, jnp.asarray(b, jnp.int32))
        last, pool = prefill(pool, b, rows[b - 1][plen:asked[b - 1]], plen)
        assert np.abs(last - want[b - 1][asked[b - 1] - 1]).max() < TOL
    for s in range(10):
        at = np.asarray([0] + [n + s for n in asked])
        tok = jnp.asarray([0] + [int(rows[b][at[b + 1]]) for b in (0, 1)],
                          jnp.int32)
        logits, pool, _ = tick(params, tok, jnp.asarray(at, jnp.int32), pool,
                               bt, jnp.asarray([False, True, True]))
        for b in (0, 1):
            assert np.abs(np.asarray(logits[b + 1])
                          - want[b][at[b + 1]]).max() < TOL
    # the shared pages were read and never written
    for p, before in zip(shared, stored):
        assert np.array_equal(np.asarray(pool[1]["kv"][p]), before)


# ---- the pieces ---------------------------------------------------------------

def test_routing_is_a_softmax_over_the_chosen_logits(cfg):
    """16 experts, the 3 largest LOGITS, no bias: the weights are a softmax
    over those three alone and add up to 1, whatever the other thirteen
    hold."""
    logit = np.array([[2.0, 1.0, 0.5, 0.4, 1.5, 1.4, -1.0, -2.0] + [0.0] * 8,
                      [-3.0, -1.0, -2.0, -4.0, -9.0, -8.0, -7.0, -6.0]
                      + [-5.0] * 8], np.float32)
    idx, weight = route_topk(jnp.asarray(logit), jnp.eye(16), None,
                             cfg.routed)
    assert np.asarray(idx).tolist() == [[0, 4, 5], [1, 2, 0]]
    for row, chosen in zip(np.asarray(weight), ([2.0, 1.5, 1.4],
                                                [-1.0, -2.0, -3.0])):
        e = np.exp(np.asarray(chosen) - max(chosen))
        assert np.allclose(row, e / e.sum(), atol=1e-6)
        assert row.sum() == pytest.approx(1.0, abs=1e-6)
    chosen, w = REFERENCE.route(jnp.asarray(logit), jnp.eye(16),
                                dict(num_experts_per_tok=3), lambda t: t)
    assert np.array_equal(chosen, idx) and np.allclose(w, weight, atol=1e-7)
    # the sigmoid form on the same logits weighs otherwise
    _, other = route_topk(jnp.asarray(logit), jnp.eye(16), jnp.zeros(16),
                          cfg.routed._replace(score="sigmoid"))
    assert np.abs(np.asarray(other) - np.asarray(weight)).max() > 0.05


def test_a_softmax_router_holds_no_bias(cfg):
    layer = hybrid.init_hybrid(cfg, 1)["layers"][0]
    assert "bias" not in layer["moe"]
    sig = hybrid.init_hybrid(cfg._replace(
        routed=cfg.routed._replace(score="sigmoid")), 1)["layers"][0]
    assert sig["moe"]["bias"].shape == (16,)


@pytest.mark.parametrize("scale", [0.125, 0.0])
def test_the_attention_scale_is_the_windows_and_the_kernels(params, sizes,
                                                            cfg, scale):
    """The gqa layer alone on random rows, under the model's 1 / head size
    and under the default root: the contiguous window, the window over pages
    and the decode kernel against the reference's attention at that scale."""
    cfg = cfg._replace(attn_scale=scale)
    lp = params["layers"][1]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (2, 41, 64)), F32)
    pos, n = jnp.zeros(2, jnp.int32), jnp.full(2, 40, jnp.int32)
    wpos = pos[:, None] + jnp.arange(40)
    cache = hybrid.init_hybrid_cache(cfg, 2, 48)[1]
    got, _ = hybrid._gqa_contiguous(lp, x[:, :40], cache, wpos,
                                    hybrid.Window(cfg, pos, n))
    page, per = 8, 6
    bt = jnp.asarray(1 + np.arange(2 * per).reshape(2, per), jnp.int32)
    pool = hybrid.init_hybrid_pool(cfg, 1 + 2 * per, page, 2, per * page)[1]
    paged, pool = hybrid._gqa_paged(
        lp, x[:, :40], pool, wpos, hybrid.Window(cfg, pos, n, bt, page))
    at = jnp.full(2, 40, jnp.int32)
    ticked, _ = hybrid._gqa_paged(
        lp, x[:, 40:], pool, at[:, None],
        hybrid.Window(cfg, at, jnp.ones(2, jnp.int32), bt, page, True, None,
                      True))
    shape = REFERENCE._static(REFERENCE.shape_of(sizes),
                              attention_multiplier=scale or 8 ** -0.5)
    f32 = jax.tree.map(lambda a: a.astype(F32), lp)
    for b in range(2):
        kc, vc = REFERENCE.keys_values(x[b], f32, shape, lambda a: a)
        ref = REFERENCE.attention(x[b], f32, kc, vc, 0, shape, lambda a: a)
        assert np.abs(np.asarray(got[b] - ref[:40])).max() < 1e-5
        assert np.abs(np.asarray(paged[b] - ref[:40])).max() < 1e-5
        assert np.abs(np.asarray(ticked[b, 0] - ref[40])).max() < 1e-5
    if scale:       # and it is not the default's result
        plain, _ = hybrid._gqa_contiguous(
            lp, x[:, :40], cache, wpos,
            hybrid.Window(cfg._replace(attn_scale=0.0), pos, n))
        assert np.abs(np.asarray(got - plain)).max() > 1e-3


def test_the_head_is_the_token_table(params, cfg):
    assert "lm_head" not in params
    assert "lm_head" not in hybrid.init_hybrid(cfg, 0)
    assert "lm_head" in hybrid.init_hybrid(cfg._replace(tied_head=False), 0)
    hidden = jnp.asarray(np.random.default_rng(3).normal(0, 1, (2, 5, 64)),
                         F32)
    got = head(params, hidden)
    assert got.shape == (2, 5, VOCAB) and got.dtype == F32
    assert np.allclose(got, np.asarray(hidden) @ np.asarray(
        params["embed"]["tok"]).T, atol=1e-5)
    # an untied model's head is read as before
    untied = dict(params, lm_head={"w": params["embed"]["tok"].T * 2})
    assert np.allclose(head(untied, hidden), 2 * np.asarray(got), atol=1e-5)
    dense = init_transformer(hybrid.TransformerConfig(
        vocab=32, layers=1, d_model=16, heads=2, d_ff=32, max_len=8))
    assert "lm_head" in dense


def test_the_two_shares_add_up_to_the_uncut_layer(cfg):
    """``first`` 0 and 8 of a 16-wide router, each share with its own 8
    experts' weights and the SAME router and shared expert: the routed parts
    of the two plus the shared expert ONCE are the uncut reference's layer
    (all 16 experts)."""
    whole = tiny_sizes(num_local_experts=16, experts_held=[0, 16])
    p = REFERENCE.make_weights(whole, 9)["layers"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (40, 64)), F32)
    valid = jnp.ones(40, bool)
    want = np.asarray(REFERENCE.routed_ffn(x, p, REFERENCE.shape_of(whole)))
    shared = np.asarray(REFERENCE._shared_expert(x, p["shared"], None))
    total, routed, held = np.zeros_like(want), 0, 0
    for first in (0, 8):
        share = dict(p, experts={k: v[first:first + 8]
                                 for k, v in p["experts"].items()})
        y, stats = moe_topk_held(x, x, share,
                                 cfg.routed._replace(first=first), valid)
        total += np.asarray(y) - shared
        by = dict(zip(MOE_STATS, np.asarray(stats)))
        assert by["pairs_dropped"] == 0 == by["pairs_misplaced"]
        routed, held = by["pairs_routed"], held + by["pairs_held"]
    assert routed == held == 40 * 3
    assert np.abs(total + shared - want).max() < 2e-5
    assert np.abs(total).max() > 0.1 < np.abs(shared).max()


def test_the_held_share_is_the_references_share(params, sizes, cfg):
    lp = params["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (40, 64)), F32)
    y, stats = moe_topk_held(x, x, lp, cfg.routed, jnp.arange(40) < 37)
    want = REFERENCE.routed_ffn(x, lp, REFERENCE.shape_of(sizes))
    assert np.abs(np.asarray(y[:37] - want[:37])).max() < 2e-5
    by = dict(zip(MOE_STATS, np.asarray(stats)))
    assert by["pairs_routed"] == 37 * 3 and 0 < by["pairs_held"] < 37 * 3


@pytest.mark.parametrize("H,P,N,G,steps", [
    (128, 64, 128, 1, 2),       # the published layer: a group in two parts
    (128, 64, 128, 8, 2),       # eight groups: four whole groups a step
    (16, 8, 16, 1, 1), (12, 8, 16, 1, 1)])
def test_the_step_at_one_group_is_the_references_step(H, P, N, G, steps):
    """One token a row against ``recurrence_step``, the reference's own:
    the state bit for bit in float32 (a product and a sum a value, the same
    two on either side), the read-out to the order of its sum; a row that is
    not active keeps its state."""
    rng = np.random.default_rng(H + G)
    B = 3
    state = rng.normal(0, 1, (B, H, P, N)).astype(np.float32)
    du = rng.normal(0, 0.1, (B, H, P)).astype(np.float32)
    a = rng.uniform(0.2, 1.0, (B, H)).astype(np.float32)
    b = rng.normal(0, 1, (B, G, N)).astype(np.float32)
    c = rng.normal(0, 1, (B, G, N)).astype(np.float32)
    active = np.array([True, False, True])
    gb, pb = pairs_a_step(G, N * 2 * P * 4, H // 2 // G)
    assert (H // 2) // (gb * pb) == steps
    y, new = ssm_decode_step(jnp.asarray(du), jnp.asarray(a), jnp.asarray(b),
                             jnp.asarray(c), pack_state(jnp.asarray(state)),
                             jnp.asarray(active))
    new = np.asarray(unpack_state(new))
    step = jax.jit(REFERENCE.recurrence_step)
    for r in (0, 2):
        ws, wy = step(state[r], a[r], du[r], np.repeat(b[r], H // G, axis=0),
                      np.repeat(c[r], H // G, axis=0))
        assert np.array_equal(new[r], np.asarray(ws))
        assert np.abs(np.asarray(y[r]) - np.asarray(wy)).max() < 2e-5
    assert np.array_equal(new[1], state[1])


def test_pairs_a_step_fits_the_steps_bytes():
    pair = 128 * 128 * 4                            # 64 KiB
    assert pairs_a_step(8, pair, 8) == (4, 8)       # whole groups
    assert pairs_a_step(1, pair, 64) == (1, 32)     # half of the one group
    assert pairs_a_step(2, pair, 48) == (1, 24)     # an equal part that fits
    assert pairs_a_step(1, pair, 4) == (1, 4)
    assert pairs_a_step(3, 4 << 20, 1) == (1, 1)    # a pair at least
    for groups, per in ((8, 8), (1, 64), (2, 48), (4, 16)):
        gb, pb = pairs_a_step(groups, pair, per)
        assert groups % gb == 0 and per % pb == 0
        assert gb * pb * pair <= 2 << 20 and (gb == 1 or pb == per)


# ---- the reference's own economies ----------------------------------------------

def test_the_reference_in_blocks_is_the_reference_whole(params, sizes, ids,
                                                        want, monkeypatch):
    """A sequence past ``TOKENS`` runs in blocks that carry a Mamba layer's
    state and tails, attend over key buffers longer than the sequence, and
    gather an expert's few rows: the same logits as the one piece."""
    monkeypatch.setattr(REFERENCE, "TOKENS", 32)
    monkeypatch.setattr(REFERENCE, "QUERIES", 16)
    got = np.asarray(REFERENCE.logits(params, sizes, ids[0], np.arange(150),
                                      keys=256))
    assert np.abs(got - want[0]).max() < TOL


def test_an_experts_rows_are_gathered_a_piece_at_a_time(monkeypatch):
    """32 rows, 8 gathered at a time: an expert 5 rows chose (one piece), one
    every row chose (four pieces), one no row chose (none): the sum written
    out, every row under its weight."""
    monkeypatch.setattr(REFERENCE, "TOKENS", 32)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 1, (32, 64)), F32)
    gate_up = jnp.asarray(rng.normal(0, 0.2, (3, 64, 48)), F32)
    down = jnp.asarray(rng.normal(0, 0.2, (3, 24, 64)), F32)
    weight = np.zeros((32, 3), np.float32)
    weight[rng.permutation(32)[:5], 0] = 0.3
    weight[:, 1] = rng.uniform(0.1, 0.5, 32)
    got = REFERENCE._experts_block(x, jnp.asarray(weight), gate_up, down,
                                   None)
    want = sum(weight[:, e:e + 1] * np.asarray(REFERENCE._swiglu(
        x, gate_up[e], down[e], lambda t: t)) for e in range(3))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(want).max() > 0.1


# ---- through the engine ---------------------------------------------------------

def greedy_choices(params, sizes, prompt, served):
    """The reference's greedy choice at each served position, teacher forced
    on ``prompt + served`` (padded on the right to one length: causal, so
    the padding is never seen, and the reference compiles once)."""
    seq = np.zeros(128, np.int32)
    n = len(prompt) + len(served)
    seq[:n] = np.concatenate([prompt, served])
    rows = np.arange(len(prompt) - 1, n - 1)
    return list(np.asarray(REFERENCE.logits(params, sizes, seq,
                                            rows)).argmax(axis=1))


@pytest.fixture(scope="module")
def decoder(params, cfg):
    return ContinuousDecoder(params, cfg, max_slots=3, max_len=160,
                             page_size=8, prefill_chunk=32)


def test_decoder_equals_the_reference_with_slots_reused(decoder, params,
                                                        sizes, ids):
    prompts = [ids[0, :40], ids[1, :71], ids[2, :9], ids[0, 50:120],
               ids[1, 30:63]]
    reqs = [decoder.submit(p, 6) for p in prompts]
    got = drain(decoder, reqs)
    for p, g in zip(prompts, got):
        assert list(g) == greedy_choices(params, sizes, p, np.asarray(g))
    stats = decoder._kv.stats
    assert stats["attn_ticks_ssm"] == stats["attn_ticks_gqa"] \
        == stats["attn_ticks_kernel"] - stats["prefill_chunks"] > 0
    assert "attn_ticks_ssm_window" not in stats
    assert 2 * 5 * 5 <= stats["ssm_state_rows"] <= 2 * 5 * 6
    assert stats["moe_pairs_dropped"] == 0 == stats["moe_pairs_misplaced"]
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]


def test_a_stored_context_is_hit_by_two_callers_at_once(params, sizes, cfg,
                                                        ids):
    """Through the engine's prefix store: a miss prefills the 64-token
    context in chunks and stores its pages AND the two ssm layers' rows; two
    callers then ask different questions about it at once: each shares the
    eight pages by reference and has the snapshot restored into its slot,
    and each continuation is the reference's greedy choice."""
    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=160,
                            page_size=8, prefill_chunk=32)
    doc = ids[0, :64]
    first = np.concatenate([doc, ids[1, :9]])
    drain(dec, [dec.submit(first, 2, prefix_key="d", prefix_len=64)])
    kv = dec._kv.stats
    assert kv["prefix_misses"] == 1 and kv["state_snapshots_stored"] == 1
    # a snapshot: two layers' (8 pairs x 16 x 16 state + 3 x 160 tail) float32
    assert dec._kv.snapshot_bytes == 2 * (8 * 16 * 16 + 3 * 160) * 4
    asks = [np.concatenate([doc, ids[1, 20:41]]),
            np.concatenate([doc, ids[2, :13]])]
    reqs = [dec.submit(p, 8, prefix_key="d", prefix_len=64) for p in asks]
    while not all(r in dec._slot_req for r in reqs):
        dec.step()
    pages = [dec._slot_pages[dec._slot_req.index(r)][:8] for r in reqs]
    assert pages[0] == pages[1]
    assert min(dec._kv._refs[pages[0]]) >= 3    # the store's and two rows'
    got = drain(dec, reqs)
    for p, g in zip(asks, got):
        assert list(g) == greedy_choices(params, sizes, p, np.asarray(g))
    assert dec.stats["prefix_hits"] == 2
    assert kv["state_snapshots_restored"] == 2 and kv["prefix_misses"] == 1
    assert kv["state_snapshot_bytes_restored"] == 2 * dec._kv.snapshot_bytes
    assert kv["prefix_tokens_shared"] == dec.stats["prefix_hit_tokens"] \
        == 2 * 64


def test_the_trash_page_reaches_no_token(params, cfg, ids, want):
    """Page 0 holds NaN on the chip (a fused kernel's idle output block):
    the window's gathered pages and the kernel must weigh it by nothing."""
    page, per = 8, 8
    window, tick = paged_programs(cfg, "kernel", page, per * page)
    pool = hybrid.init_hybrid_pool(cfg, 1 + 2 * per, page, 2, per * page)
    pool[1]["kv"] = pool[1]["kv"].at[0].set(jnp.nan)
    bt = jnp.asarray(1 + np.arange(2 * per).reshape(2, per), jnp.int32)
    last, pool = window(params, jnp.asarray(ids[:1, :32]),
                        jnp.zeros(1, jnp.int32), pool, bt[:1],
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray([20], jnp.int32))
    assert np.abs(np.asarray(last[0]) - want[0, 19]).max() < TOL
    logits, _, _ = tick(params, jnp.asarray(ids[:2, 20]),
                        jnp.asarray([20, 0], jnp.int32), pool, bt,
                        jnp.asarray([True, False]))
    assert np.abs(np.asarray(logits[0]) - want[0, 20]).max() < TOL


# ---- what is refused ------------------------------------------------------------

@pytest.mark.parametrize("change,message", [
    (lambda c: c._replace(routed=c.routed._replace(score="sparsemax")),
     "sigmoid \\| softmax"),
    (lambda c: c._replace(ssm=c.ssm._replace(heads=7)),
     "pairs inside a group"),
    (lambda c: c._replace(routed=c.routed._replace(count=4)),
     "at least 8"),
])
def test_config_is_checked(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        hybrid.check_config(change(cfg))


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    low = cfg._replace(dtype=jnp.bfloat16)
    got = program_logits(params, ids[:1, :64], low)
    assert np.abs(got - want[:1, :64]).max() > 100 * TOL
