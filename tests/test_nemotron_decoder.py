"""The hybrid decoder's state-space mixer, its grouped-query layer without
positions at 16 query heads a KV head, routed relu^2 experts in a latent with
a share and a shared expert, and a layer without a feed-forward (Nemotron 3
Super's shape) against their plain reference, at tiny widths: hidden 64, 16
heads over 1 KV head of 8, Mamba-2 of 8 heads of 8 on a state 16 wide (NOT
square) in 2 groups, 4 taps, chunks of 8; a router 32 wide of which experts
0-7 are held, 6 a token, x5, latent 32, experts 48 wide, shared 64; published
layers ``*EMEM``: the block layers (gqa, moe), (ssm, moe), (ssm, none).

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums: the chunked scan sums a chunk's tokens at once where the reference
steps a token at a time, and relu^2 squares what the sums left, so logits of
scale 0.1-0.5 agree to ~2e-6; ``TOL`` 5e-5 leaves room for the online
softmax's page-by-page sums. A bfloat16 run of the program misses it by two
orders of magnitude (the last test), so computing in a lower precision than
stated cannot pass.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo.transformer import transformer_apply
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.ops.grouped_matmul import (RUN, TILE, _runs,
                                             grouped_swiglu,
                                             product_steps)
from mmlspark_tpu.ops.ssm_step import (pack_state, ssm_decode_step,
                                       unpack_state)
from mmlspark_tpu.parallel.moe import MOE_STATS, moe_topk_held
from mmlspark_tpu.serving.continuous import (ContinuousDecoder,
                                             derived_page_size)
from mmlspark_tpu.serving.kv_pool import PagedKVPool
from test_ling_decoder import drain, paged_programs, paged_run

TOL = 5e-5
VOCAB = 97
REFERENCE = bench_run.load_by_path("references", "nemotron_h")
DRIVER = bench_run.load_by_path("drivers", "generate_nemotron")
F32 = jnp.float32
CONFIG = os.path.join(bench_run.HERE, "configs",
                      "nemotron3_super_ep4_l11.json")


def tiny_sizes(**changes):
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(CONFIG) as fh:
        config = json.load(fh)
    return dict(dict(
        config, hidden_size=64, num_attention_heads=16, num_key_value_heads=1,
        head_dim=8, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
        n_groups=2, chunk_size=8, moe_latent_size=32,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=64,
        n_routed_experts=8, experts_held=[0, 8],
        published=dict(config["published"], n_routed_experts=32),
        num_experts_per_tok=6, vocab_size=VOCAB,
        hybrid_override_pattern="*EMEM", layers_held=[36, 37, 38, 39, 40],
        num_hidden_layers=5, compute_dtype="float32", param_dtype="float32"),
        **changes)


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
    return np.asarray(hidden.astype(F32) @ params["lm_head"]["w"].astype(F32))


def recurrence(u, b, c, d, a_rate, state):
    """The plain recurrence in numpy, float64: ``u`` (H, W, P), ``b``, ``c``
    (G, W, N), ``d`` (H, W), ``state`` (H, P, N) -> (y (H, W, P), state)."""
    u, b, c, d, a_rate, state = (np.asarray(t, np.float64)
                                 for t in (u, b, c, d, a_rate, state))
    H, W, _ = u.shape
    per = H // b.shape[0]
    y = np.zeros_like(u)
    for t in range(W):
        for h in range(H):
            state[h] = (np.exp(d[h, t] * a_rate[h]) * state[h]
                        + d[h, t] * np.outer(u[h, t], b[h // per, t]))
            y[h, t] = state[h] @ c[h // per, t]
    return y, state


# ---- the mapping --------------------------------------------------------------

def test_mapping_keeps_the_published_numbers(cfg):
    assert cfg.mixers == ("gqa", "ssm", "ssm")
    assert cfg.ffn == ("moe", "moe", "none")
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups, r.groups_kept,
            r.scale, r.d_expert, r.d_shared, r.latent, r.form) == (
                32, 0, 8, 6, 1, 1, 5.0, 48, 64, 32, "relu2")
    assert cfg.ssm == (8, 8, 16, 2, 4, 8)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (16, 1, 8)
    assert cfg.norm_eps == 1e-5 and cfg.qk_positions is False


def test_the_real_file_maps_at_its_published_widths():
    with open(CONFIG) as fh:
        config = json.load(fh)
    cfg = DRIVER.program_config(config, 4096)
    hybrid.check_config(cfg)
    assert cfg.mixers == ("gqa",) + ("ssm",) * 5
    assert cfg.ffn == ("moe",) * 5 + ("none",)
    assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim,
            cfg.vocab) == (4096, 32, 2, 128, 32768)
    assert cfg.ssm == (128, 64, 128, 8, 4, 128)
    assert cfg.routed[:9] == (512, 0, 128, 22, 1, 1, 5.0, 2688, 5376)
    assert (cfg.routed.latent, cfg.routed.form) == (1024, "relu2")
    assert derived_page_size(cfg, 4096) == 256
    shapes = hybrid.pool_shapes(cfg, 17, 256, 32, 4096)
    assert shapes[0] == {"kv": ((17, 2, 256, 256), jnp.bfloat16)}
    # the state is not square: 64 x 128 a head, held transposed in pairs
    assert shapes[1]["state"] == ((32, 64, 128, 128), F32)
    assert shapes[1]["conv"] == ((32, 3, 10240), jnp.bfloat16)


@pytest.mark.parametrize("pattern,entries", [
    ("*EMEMEMEMEM", [("attention", True)] + [("mamba", True)] * 4
     + [("mamba", False)]),
    ("MEM*E", [("mamba", True), ("mamba", False), ("attention", True)]),
])
def test_published_layers_pair_into_block_layers(pattern, entries):
    assert REFERENCE.block_layers(pattern) == entries


@pytest.mark.parametrize("pattern", ["EM", "MEE", "M-"])
def test_a_pattern_that_does_not_pair_is_refused(pattern):
    with pytest.raises(ValueError, match="no mixer"):
        REFERENCE.block_layers(pattern)


# ---- against the reference: logits --------------------------------------------

def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL


@pytest.mark.parametrize("kind,layer", [("ssm", 1), ("gqa", 0)])
def test_a_mixer_alone_matches_the_reference(params, sizes, cfg, kind, layer):
    """One mixer on random rows: the contiguous window from position 0
    against the reference's function of the whole sequence."""
    lp = params["layers"][layer]
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (2, 45, 64)), F32)
    pos = jnp.zeros(2, jnp.int32)
    n = jnp.full(2, 45, jnp.int32)
    cache = hybrid.init_hybrid_cache(cfg, 2, 48)[layer]
    if kind == "ssm":
        got, new = hybrid._ssm_layer(lp, x, cache, None,
                                     hybrid.Window(cfg, pos, n))
        ref = REFERENCE.mamba
        pre = (x @ lp["in"]["w"])[..., 64:64 + 64 + 2 * 2 * 16]
        assert np.allclose(new["conv"], pre[:, -3:], atol=1e-6)
    else:
        wpos = pos[:, None] + jnp.arange(45)
        got, _ = hybrid._gqa_contiguous(lp, x, cache, wpos,
                                        hybrid.Window(cfg, pos, n))
        ref = REFERENCE.attention
    shape = REFERENCE.shape_of(sizes)
    for b in range(2):
        want = ref(x[b], jax.tree.map(lambda a: a.astype(F32), lp), shape,
                   lambda a: a)
        assert np.abs(np.asarray(got[b] - want)).max() < 1e-5


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 70, 100 and 33 tokens in windows of 32 (state and tails
    carried over two and three window boundaries, four scan chunks of 8 a
    window, the last window padded), then 12 ticks through the state-space
    step and the grouped-query kernel (``kernel``) or the chunked scan and
    the gathered pages (``gather``): the reference's logits at every
    position served."""
    lens = [70, 100, 33]
    firsts, ticks, _, counts = paged_run(params, ids, cfg, impl, lens, 12)
    for b, n in enumerate(lens):
        assert np.abs(firsts[b] - want[b, n - 1]).max() < TOL
        assert np.abs(ticks[b] - want[b, n:n + 12]).max() < TOL
    by = dict(zip(MOE_STATS, counts.sum(axis=0)))
    # 3 rows x 6 experts a token x 2 routed layers x 12 ticks over 32 experts
    assert by["pairs_routed"] == 3 * 6 * 2 * 12
    assert 0 < by["pairs_held"] < by["pairs_routed"]
    assert by["pairs_dropped"] == 0 and by["pairs_misplaced"] == 0


def test_a_window_that_ends_mid_chunk_leaves_the_right_state(params, ids,
                                                             cfg):
    """A window of 32 lanes of which 19 are real (a scan chunk of 8 cut at
    lane 3): state and tails are those after lane 18, whatever the padding
    lanes hold, and the next window continues from them as whole windows
    would."""
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
    for a, b in zip(both[1:], whole[1:]):
        for key in ("state", "conv"):
            assert np.abs(np.asarray(a[key] - b[key])).max() < 1e-5
    assert np.abs(np.asarray(part[1]["state"] - whole[1]["state"])).max() \
        > 1e-3


# ---- the scan and the step ----------------------------------------------------

@pytest.mark.parametrize("W,chunk", [(8, 8), (21, 8), (32, 8), (5, 128),
                                     (40, 16)])
def test_the_chunked_scan_is_the_recurrence(W, chunk):
    """Random inputs with steps up to 3 (a decay of ``exp(-48)`` a token:
    what a ratio of two powers could not hold), a state that is not square,
    two heads a group; padding lanes (step 0) leave the state as the last
    real lane did. 1e-4: a decay's exponent is the difference of two
    cumulated sums that reach -770 in a chunk of 16 here, where float32
    resolves 6e-5 (the model's steps are under 0.3 and its sums under 400)."""
    rng = np.random.default_rng(W)
    B, H, P, N, G = 2, 4, 6, 10, 2
    u = rng.normal(0, 1, (B, H, W, P)).astype(np.float32)
    b = rng.normal(0, 1, (B, G, W, N)).astype(np.float32)
    c = rng.normal(0, 1, (B, G, W, N)).astype(np.float32)
    d = rng.uniform(0.001, 3.0, (B, H, W)).astype(np.float32)
    n_valid = np.array([W, max(1, W - 3)])
    d[1, :, n_valid[1]:] = 0.0
    a_rate = -rng.uniform(1, 16, H).astype(np.float32)
    state = rng.normal(0, 1, (B, H, P, N)).astype(np.float32)
    y, new = hybrid.ssm_chunk(*(jnp.asarray(t) for t in (u, b, c, d, a_rate,
                                                         state)), chunk)
    for r in range(B):
        n = n_valid[r]
        wy, ws = recurrence(u[r, :, :n], b[r, :, :n], c[r, :, :n],
                            d[r, :, :n], a_rate, state[r])
        assert np.abs(np.asarray(y[r, :, :n]) - wy).max() < 1e-4
        assert np.abs(np.asarray(new[r]) - ws).max() < 1e-4


@pytest.mark.parametrize("H,P,N,G", [(8, 8, 16, 2), (16, 4, 8, 1),
                                     (4, 8, 32, 2)])
def test_the_pallas_step_is_one_step_of_the_recurrence(H, P, N, G):
    """One token a row against the recurrence (exact products in float32:
    2e-6), the state aliased through the pool's layout and back; a row that
    is not active keeps its state bit for bit."""
    rng = np.random.default_rng(H + N)
    B = 3
    state = rng.normal(0, 1, (B, H, P, N)).astype(np.float32)
    u = rng.normal(0, 1, (B, H, P)).astype(np.float32)
    d = rng.uniform(0.001, 0.5, (B, H)).astype(np.float32)
    a_rate = -rng.uniform(1, 16, H).astype(np.float32)
    b = rng.normal(0, 1, (B, G, N)).astype(np.float32)
    c = rng.normal(0, 1, (B, G, N)).astype(np.float32)
    active = np.array([True, False, True])
    packed = pack_state(jnp.asarray(state))
    assert packed.shape == (B, H // 2, N, 2 * P)
    assert np.array_equal(unpack_state(packed), state)
    y, new = ssm_decode_step(jnp.asarray(u * d[..., None]),
                             jnp.exp(jnp.asarray(d * a_rate)), b, c, packed,
                             jnp.asarray(active))
    new = np.asarray(unpack_state(new))
    for r in (0, 2):
        wy, ws = recurrence(u[r][:, None], b[r][:, None], c[r][:, None],
                            d[r][:, None], a_rate, state[r])
        assert np.abs(np.asarray(y[r]) - wy[:, 0]).max() < 2e-6
        assert np.abs(new[r] - ws).max() < 2e-6
    assert np.array_equal(new[1], state[1])


def test_a_pair_of_heads_lies_inside_a_group():
    z = jnp.zeros
    with pytest.raises(ValueError, match="pair of heads"):
        ssm_decode_step(z((1, 6, 4)), z((1, 6)), z((1, 2, 8)), z((1, 2, 8)),
                        z((1, 3, 8, 8)), jnp.ones(1, bool))


# ---- the grouped-query fold at 16 a head ---------------------------------------

def gqa_case(H, Hkv, hd=8, B=3, page=8, per=4, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(0, 1, (B, H, hd)).astype(np.float32)
    k = rng.normal(0, 1, (B, Hkv, hd)).astype(np.float32)
    v = rng.normal(0, 1, (B, Hkv, hd)).astype(np.float32)
    pool = rng.normal(0, 1, (1 + B * per, Hkv, page, 2 * hd)).astype(
        np.float32)
    pool[0] = np.nan                                    # the trash page
    bt = (1 + np.arange(B * per).reshape(B, per)).astype(np.int32)
    pos = np.array([0, 13, 31], np.int32)[:B]
    return q, k, v, pool, bt, pos


@pytest.mark.parametrize("H,Hkv", [(16, 1), (32, 2), (48, 3)])
def test_sixteen_query_heads_a_kv_head_match_the_oracle(H, Hkv):
    """Two whole groups of state rows on one KV head: contexts against a
    float64 softmax over each row's keys, the fresh row written in place."""
    q, k, v, pool, bt, pos = gqa_case(H, Hkv)
    got, new = pa.paged_attention_gqa(*(jnp.asarray(a) for a in (
        q, k, v, pool, bt, pos)))
    hd, page = q.shape[-1], pool.shape[2]
    for b, p in enumerate(pos):
        rows = np.concatenate([pool[j] for j in bt[b]], axis=1)[:, :p + 1]
        rows = rows.astype(np.float64)
        rows[:, p] = np.concatenate([k[b], v[b]], axis=-1)
        for h in range(H):
            kv = rows[h // (H // Hkv)]
            s = kv[:, :hd] @ q[b, h] / np.sqrt(hd)
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ kv[:, hd:]
            assert np.abs(np.asarray(got[b, h]) - want).max() < 2e-6
        assert np.array_equal(
            np.asarray(new[bt[b, p // page], :, p % page]),
            np.concatenate([k[b], v[b]], axis=-1))


def test_a_kv_head_serves_up_to_sixteen():
    q, k, v, pool, bt, pos = (jnp.asarray(a) for a in gqa_case(24, 1))
    with pytest.raises(ValueError, match="1, 2, 4"):
        pa.paged_attention_gqa(q, k, v, pool, bt, pos)


# ---- the routed feed-forward in a latent ----------------------------------------

def test_the_relu2_product_is_the_plain_product():
    """Three tiles over two of four experts (one expert twice), a tile past
    the bound not written."""
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (4 * TILE, 32)).astype(np.float32)
    up = rng.normal(0, 0.2, (4, 32, 48)).astype(np.float32)
    down = rng.normal(0, 0.2, (4, 48, 32)).astype(np.float32)
    experts = np.array([2, 2, 0, 3], np.int32)
    got = np.asarray(grouped_swiglu(jnp.asarray(x), jnp.asarray(experts), 3,
                                    jnp.asarray(up), jnp.asarray(down),
                                    gated=False))
    for s, e in enumerate(experts[:3]):
        rows = x[s * TILE:(s + 1) * TILE]
        want = np.square(np.maximum(rows @ up[e], 0.0)) @ down[e]
        assert np.abs(got[s * TILE:(s + 1) * TILE] - want).max() < 1e-5


def one_tile_walk(x, tile_expert, total, gate_up, down, gated):
    """The product a tile a grid step, as it stood before a step became an
    expert's run of tiles: the oracle a run's one product is held to, bit
    for bit."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(expert_ref, x_ref, gu_ref, dn_ref, o_ref):
        rows = x_ref[...]
        gu = jnp.dot(rows, gu_ref[0], preferred_element_type=F32)
        if gated:
            f = gu.shape[1] // 2
            h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        else:
            h = jnp.square(jnp.maximum(gu, 0.0))
        o_ref[...] = jnp.dot(h.astype(rows.dtype), dn_ref[0],
                             preferred_element_type=F32)

    @jax.jit
    def call(tile_expert, total, x, gate_up, down):
        R, D = x.shape
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(total,),
                in_specs=[
                    pl.BlockSpec((TILE, D), lambda s, e: (s, 0)),
                    pl.BlockSpec((1, D, gate_up.shape[2]),
                                 lambda s, e: (e[s], 0, 0)),
                    pl.BlockSpec((1, down.shape[1], D),
                                 lambda s, e: (e[s], 0, 0))],
                out_specs=pl.BlockSpec((TILE, D), lambda s, e: (s, 0))),
            out_shape=jax.ShapeDtypeStruct((R, D), F32),
            interpret=True)(tile_expert, x, gate_up, down)

    return call(tile_expert, jnp.asarray(total, jnp.int32), x, gate_up, down)


#: tiles an expert (0: no row reached it) and tiles of the layout past the
#: bound: a run is up to RUN tiles of one expert
RUNS = {
    "one_tile_each": ((1, 1, 1), 2),
    "two": ((2,), 1),
    "a_whole_run": ((RUN,), 1),
    "a_run_and_a_tile": ((RUN + 1,), 1),
    "two_runs_and_three": ((2 * RUN + 3,), 0),
    "mixed_with_idle_experts": ((0, 3, 1, 0, RUN + 2, 2, 0), 3),
    "ends_at_the_bound": ((1, 4), 0),
    "nothing": ((0, 0), 2),
}


def run_case(tiles, spare, dtype, gated, seed=5):
    """Rows, the layout's vectors and weights for experts of ``tiles`` tiles
    each, ``spare`` tiles past the bound; NaN in the weights of every expert
    no row reached."""
    rng = np.random.default_rng(seed)
    E, D, Fw = len(tiles), 32, 48
    total = sum(tiles)
    experts = np.concatenate([np.repeat(np.arange(E), tiles),
                              np.full(spare, E - 1)]).astype(np.int32)
    x = rng.normal(0, 1, ((total + spare) * TILE, D)).astype(np.float32)
    up = rng.normal(0, 0.2, (E, D, 2 * Fw if gated else Fw)).astype(np.float32)
    down = rng.normal(0, 0.2, (E, Fw, D)).astype(np.float32)
    idle = np.asarray(tiles) == 0
    up[idle], down[idle] = np.nan, np.nan
    return (jnp.asarray(x, dtype), jnp.asarray(experts), total,
            jnp.asarray(up, dtype), jnp.asarray(down, dtype))


def plain_product(x, up, down, gated):
    h = np.asarray(x, np.float32) @ np.asarray(up, np.float32)
    f = h.shape[1] // 2
    h = (h[:, :f] / (1 + np.exp(-h[:, :f])) * h[:, f:] if gated
         else np.square(np.maximum(h, 0.0)))
    return h @ np.asarray(down, np.float32)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(RUNS))
def test_a_run_of_tiles_is_one_product(case, dtype, gated):
    """An expert's run of tiles multiplied in one grid step gives every row
    under the bound what the tile-by-tile walk gave it, reads no expert
    without a row (their weights hold NaN) and writes no tile past the
    bound. Bit for bit in bfloat16, the served dtype, and in any run of one
    tile; a float32 run of several is held to 1e-5 of the plain product:
    XLA:CPU picks a float32 dot's summation order by its shape, 16 rows or
    ``RUN * TILE``, and the last bit follows it."""
    tiles, spare = RUNS[case]
    x, experts, total, up, down = run_case(tiles, spare, dtype, gated)
    got = np.asarray(grouped_swiglu(x, experts, total, up, down,
                                    interpret=True, gated=gated))
    want = np.asarray(one_tile_walk(x, experts, total, up, down, gated))
    n = total * TILE
    assert np.isfinite(got[:n]).all()
    if dtype == "bfloat16" or max(tiles) <= 1:
        np.testing.assert_array_equal(got[:n], want[:n])
    for s, e in enumerate(np.asarray(experts)):
        rows = slice(s * TILE, (s + 1) * TILE)
        plain = plain_product(x[rows], up[e], down[e], gated)
        if s >= total:
            # whatever the buffer held, not these rows' product
            assert not np.isclose(got[rows], plain, rtol=1e-3).any()
        elif dtype == "float32":
            assert np.abs(got[rows] - plain).max() < 1e-5
            assert np.abs(want[rows] - plain).max() < 1e-5


@pytest.mark.parametrize("case", sorted(RUNS))
def test_the_schedule_is_the_layouts_runs(case):
    """``_runs``: an expert's tiles RUN at a time, in the layout's order; an
    operand of ``x`` moves only to a tile of the step's run."""
    tiles, spare = RUNS[case]
    _, experts, total, _, _ = run_case(tiles, spare, "float32", True)
    steps, expert, first, count, tile_of = (
        np.asarray(a) for a in _runs(experts, jnp.asarray(total, jnp.int32)))
    want = [(e, sum(tiles[:e]) + at, min(RUN, n - at))
            for e, n in enumerate(tiles) for at in range(0, n, RUN)]
    assert steps == len(want) == int(product_steps(jnp.asarray(tiles)))
    assert list(zip(expert[:steps], first[:steps], count[:steps])) == want
    held = np.zeros(RUN, np.int64)
    for k, (_, at, n) in enumerate(want):
        held[:n] = at + np.arange(n)
        np.testing.assert_array_equal(tile_of[:, k], held)


def test_the_four_shares_add_up_to_the_uncut_layer(params, sizes, cfg):
    """``first`` 0, 8, 16, 24 of a 32-wide router, each share with its own 8
    experts' weights and the SAME router, latent projections and shared
    expert: the routed parts of the four plus the shared expert ONCE are the
    uncut reference's layer (all 32 experts)."""
    whole = tiny_sizes(n_routed_experts=32, experts_held=[0, 32])
    p = REFERENCE.make_weights(whole, 9)["layers"][0]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (40, 64)), F32)
    valid = jnp.ones(40, bool)
    want = np.asarray(REFERENCE.routed_ffn(x, p, REFERENCE.shape_of(whole)))
    shared = np.asarray(REFERENCE._shared_expert(x, p["shared"], None))
    total, routed, held = np.zeros_like(want), 0, 0
    for first in (0, 8, 16, 24):
        share = dict(p, experts={k: v[first:first + 8]
                                 for k, v in p["experts"].items()})
        y, stats = moe_topk_held(x, x, share,
                                 cfg.routed._replace(first=first), valid)
        total += np.asarray(y) - shared
        by = dict(zip(MOE_STATS, np.asarray(stats)))
        assert by["pairs_dropped"] == 0 == by["pairs_misplaced"]
        routed, held = by["pairs_routed"], held + by["pairs_held"]
    assert routed == held == 40 * 6
    assert np.abs(total + shared - want).max() < 2e-5
    assert np.abs(total).max() > 0.1 < np.abs(shared).max()


def test_the_held_share_is_the_references_share(params, sizes, cfg):
    lp = params["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (40, 64)), F32)
    y, stats = moe_topk_held(x, x, lp, cfg.routed, jnp.arange(40) < 37)
    want = REFERENCE.routed_ffn(x, lp, REFERENCE.shape_of(sizes))
    assert np.abs(np.asarray(y[:37] - want[:37])).max() < 2e-5
    assert dict(zip(MOE_STATS, np.asarray(stats)))["pairs_routed"] == 37 * 6


def test_what_an_idle_row_holds_reaches_no_token(params, cfg):
    """A row that is no token may hold NaN (an idle row's context): the
    latent projection must not carry it into another row's sum."""
    lp = params["layers"][0]["moe"]
    x = np.random.default_rng(6).normal(0, 1, (40, 64)).astype(np.float32)
    clean, _ = moe_topk_held(jnp.asarray(x), jnp.asarray(x), lp, cfg.routed,
                             jnp.arange(40) < 37)
    x[37:] = np.nan
    dirty, _ = moe_topk_held(jnp.asarray(x), jnp.asarray(x), lp, cfg.routed,
                             jnp.arange(40) < 37)
    assert np.array_equal(np.asarray(clean[:37]), np.asarray(dirty[:37]))


# ---- through the engine ---------------------------------------------------------

def greedy_choices(params, sizes, prompt, served):
    """The reference's greedy choice at each served position, teacher forced
    on ``prompt + served`` (padded on the right to one length: causal, so
    the padding is never seen, and the reference compiles once): equal to
    ``served`` exactly when the decoder's continuation is the reference's."""
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
    """Five requests through three slots: a released and re-admitted slot
    starts from a zero state and zero tails (else its tokens would differ);
    every tick of the ssm layers ran the Pallas step, every tick of the gqa
    layer the grouped-query kernel, and the pool counted the states the
    steps moved."""
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
    # two ssm layers a live row a tick: a request's five tokens after its
    # first come out of ticks, and the pipeline dispatches one tick more
    # before the row retires
    assert 2 * 5 * 5 <= stats["ssm_state_rows"] <= 2 * 5 * 6
    assert stats["moe_pairs_dropped"] == 0 == stats["moe_pairs_misplaced"]
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]


def test_under_gather_the_ticks_count_off_the_step(params, cfg, ids):
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=96,
                            page_size=8, prefill_chunk=32,
                            paged_attn="gather")
    drain(dec, [dec.submit(ids[0, :20], 4)])
    stats = dec._kv.stats
    assert stats["attn_ticks_ssm_window"] == stats["attn_ticks_gqa_window"] \
        > 0
    assert "attn_ticks_ssm" not in stats and stats["ssm_state_rows"] == 0


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
    clean_last, clean = window(*args, pool, *tail)
    dirty_last, after = window(*args, dirty, *tail)
    assert np.array_equal(np.asarray(clean_last), np.asarray(dirty_last))
    for a, b in zip(clean[1:], after[1:]):
        assert np.array_equal(np.asarray(a["state"]), np.asarray(b["state"]))


def test_the_pool_holds_state_and_tails_a_slot(cfg):
    pool = PagedKVPool(cfg, num_pages=9, page_size=8, slots=2,
                       slot_positions=64)
    assert set(pool.buffers[0]) == {"kv"}
    assert pool.buffers[0]["kv"].shape == (9, 1, 8, 16)
    assert pool.buffers[1]["state"].shape == (2, 4, 16, 16)
    assert pool.buffers[2]["conv"].shape == (2, 3, 64 + 2 * 2 * 16)
    # a snapshot is the two ssm layers' state and tails of one slot
    assert pool.snapshot_bytes == 2 * (4 * 16 * 16 + 3 * 128) * 4
    label, states = hybrid.accountants(cfg, hybrid.Geometry(8, 8, True))
    assert (label.layers, states.layers) == (1, 2)
    pool.note(states.decode([5, 9, 30], rows=4, context=31))
    assert pool.stats["ssm_state_rows"] == 2 * 3
    assert pool.stats["attn_ticks_ssm"] == 1


def test_the_trash_page_reaches_no_token(params, cfg, ids, want):
    """Page 0 holds NaN on the chip (a fused kernel's idle output block):
    the window's gathered pages and the wide fold must weigh it by nothing."""
    page, per = 8, 8
    window, tick = paged_programs(cfg, "kernel", page, per * page)
    pool = hybrid.init_hybrid_pool(cfg, 1 + 2 * per, page, 2, per * page)
    pool[0]["kv"] = pool[0]["kv"].at[0].set(jnp.nan)
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

@pytest.mark.parametrize("kwargs,reason", [
    (dict(kv_dtype="int8"), "an ssm layer's state is float32"),
    (dict(draft_params={}, draft_cfg=None), "ssm layer's state and tails"),
    (dict(mesh="a mesh"), "the ssm step"),
])
def test_refused_combinations_say_why(params, cfg, kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        ContinuousDecoder(params, cfg, max_slots=2, max_len=64, **kwargs)


@pytest.mark.parametrize("change,message", [
    (lambda c: c._replace(mixers=("gqa", "mamba", "ssm")),
     "unknown mixer.*conv \\| gqa \\| ssm"),
    (lambda c: c._replace(ssm=None), "cfg.ssm"),
    (lambda c: c._replace(ssm=c.ssm._replace(taps=1)), "cfg.ssm"),
    (lambda c: c._replace(ssm=c.ssm._replace(heads=6, groups=2)),
     "pairs inside a group"),
    (lambda c: c._replace(ffn=("moe", "moe", "nothing")),
     "dense \\| moe \\| none"),
    (lambda c: c._replace(routed=c.routed._replace(form="gelu")),
     "swiglu \\| relu2"),
    (lambda c: c._replace(heads=24, kv_heads=1), "gqa layers"),
])
def test_config_is_checked(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        hybrid.check_config(change(cfg))


def test_session_checkpoint_names_the_state(decoder, ids):
    req = decoder.submit(ids[0, :12], 3)
    with pytest.raises(ValueError, match="ssm layer's state"):
        decoder.checkpoint_session(req)
    drain(decoder, [req])


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    low = cfg._replace(dtype=jnp.bfloat16)
    got = program_logits(params, ids[:1, :64], low)
    assert np.abs(got - want[:1, :64]).max() > 20 * TOL
