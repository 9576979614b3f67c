"""The hybrid decoder with latent attention in EVERY layer (GLM-4.7-Flash's
shape: a head count that is no multiple of the kernel's tile, a query through
its own normed latent, unequal key and value widths, no gate, a shared expert
beside routed experts held whole) against its plain reference, at tiny
widths: hidden 64, 5 heads of 12 + 8 / 16 under a rank-24 query and a rank-32
key/value latent, layers ``dense, moe, moe, moe``, 8 routed experts of width
32 (top-2, x1.8) and one shared expert.

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums: logits of scale 0.1-0.6 agree to ~1e-6, and ``TOL`` 5e-5 leaves
room for the online softmax's tile-by-tile and page-by-page sums against the
reference's one softmax a row, and for the absorbed form's other order of
products. A bfloat16 run of the program misses it by two orders of magnitude
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
from mmlspark_tpu.models.zoo.transformer import transformer_apply
from mmlspark_tpu.observability import tracing
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.parallel.moe import MOE_STATS, moe_topk_held
from mmlspark_tpu.serving.continuous import (ContinuousDecoder,
                                             derived_page_size)
from mmlspark_tpu.serving.kv_pool import PagedKVPool
from test_ling_decoder import drain, paged_run

TOL = 5e-5
VOCAB = 97
REFERENCE = bench_run.load_by_path("references", "glm4_moe_lite")
DRIVER = bench_run.load_by_path("drivers", "generate_glm")
F32 = jnp.float32
CONFIG = os.path.join(bench_run.HERE, "configs", "glm47_flash_l7.json")


def tiny_sizes():
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(CONFIG) as fh:
        config = json.load(fh)
    return dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=5,
        num_key_value_heads=5, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        experts_held=[0, 8], vocab_size=VOCAB, layers_held=[0, 7, 8, 9],
        num_hidden_layers=4, compute_dtype="float32", param_dtype="float32")


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


def test_mapping_keeps_the_published_numbers(cfg):
    assert cfg.mixers == ("mla",) * 4
    assert cfg.ffn == ("dense", "moe", "moe", "moe")
    r = cfg.routed
    assert (r.experts, r.first, r.held, r.per_token, r.groups, r.groups_kept,
            r.scale, r.d_expert, r.d_shared) == (8, 0, 8, 2, 1, 1, 1.8, 32, 32)
    assert cfg.latent == (32, 12, 8, 16, 24, False)
    assert cfg.norm_eps == 1e-5 and cfg.rope_theta == 1e6
    assert cfg.heads == 5


def test_the_real_file_maps_at_its_published_widths():
    with open(CONFIG) as fh:
        config = json.load(fh)
    cfg = DRIVER.program_config(config, 32768)
    hybrid.check_config(cfg)
    assert cfg.mixers == ("mla",) * 7
    assert cfg.ffn == ("dense",) + ("moe",) * 6
    assert (cfg.d_model, cfg.heads, cfg.d_ff, cfg.vocab) == (
        2048, 20, 10240, 154880)
    assert cfg.routed[:9] == (64, 0, 0, 4, 1, 1, 1.8, 1536, 1536)
    assert cfg.routed.held == 64
    assert cfg.latent == (512, 192, 64, 256, 768, False)
    # the pool's row is the padded one Ling's is: 576 -> 640 values
    assert hybrid.latent_row(cfg) == 640
    # latent rows are a dense pool: sixteen pages a slot, held to 16..256
    assert derived_page_size(cfg, 32768) == 256
    # a tile of the window's fold is _KEY_TILE keys: 8 pages of 256
    assert hybrid.window_tile(256, 128) == 8
    assert hybrid.window_tile(8, 3) == 3 and hybrid.window_tile(4096, 8) == 1


def test_parameters_follow_the_latent_sizes(cfg):
    """``init_hybrid`` gives the query its two products and norm and leaves
    out the gate; Ling's sizes keep the one product and the gate."""
    lp = hybrid.init_hybrid(cfg, 0)["layers"][1]
    assert {k: lp[k]["w"].shape for k in ("q_a", "q_b", "kva", "kvb", "o")} \
        == {"q_a": (64, 24), "q_b": (24, 100), "kva": (64, 40),
            "kvb": (32, 140), "o": (80, 64)}
    assert "z" not in lp and "q" not in lp and lp["q_norm"]["scale"].shape \
        == (24,)
    assert sorted(lp["moe"]) == ["bias", "experts", "router", "shared"]
    ling = hybrid.init_hybrid(cfg._replace(
        latent=cfg.latent._replace(q_rank=0, gate=True)), 0)["layers"][1]
    assert ling["q"]["w"].shape == (64, 100) and ling["z"]["w"].shape \
        == (64, 5) and "q_a" not in ling


def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL


def test_the_mixer_alone_matches_the_reference(params, sizes, cfg):
    """One layer's latent attention on random rows: the contiguous window
    from position 0 against the reference's function of the whole
    sequence."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (2, 45, 64)), F32)
    n = jnp.full(2, 45, jnp.int32)
    wpos = jnp.zeros((2, 1), jnp.int32) + jnp.arange(45)
    cache = hybrid.init_hybrid_cache(cfg, 2, 48)[1]
    got, _ = hybrid.KINDS["mla"].contiguous(
        lp, x, cache, wpos, hybrid.Window(cfg, wpos[:, 0], n))
    for b in range(2):
        ref = REFERENCE.mla(x[b], lp, REFERENCE.shape_of(sizes), lambda a: a)
        assert np.abs(np.asarray(got[b] - ref)).max() < 1e-5


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 70, 100 and 33 tokens in chunks of 32 (each window over its
    row's latent pages, tile by tile), then 12 ticks through the absorbed
    kernel with 5 heads padded to 8 (``kernel``) or the tiled window
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


# ---- the window over latent pages ---------------------------------------------

def window_case(seed, B, W, page, P, la, H, fill):
    """Latent pages, block tables and a window's queries: rows at
    ``fill`` positions already, ``W`` lanes of which the row's last are
    padding."""
    rng = np.random.default_rng(seed)
    row = hybrid._round_up(la.latent + la.rope, 128)
    N = 1 + B * P
    pool = rng.normal(size=(N, 1, page, row)).astype(np.float32)
    pool[0] = np.nan                                # the trash page
    bt = 1 + rng.permutation(B * P).reshape(B, P).astype(np.int32)
    q = rng.normal(size=(B, H, W, la.nope + la.rope)).astype(np.float32)
    w = rng.normal(0, 0.2, (la.latent, H, la.nope + la.value)).astype(
        np.float32)
    wpos = np.asarray(fill, np.int32)[:, None] + np.arange(W, dtype=np.int32)
    return map(jnp.asarray, (q, pool, bt, wpos, w))


@pytest.mark.parametrize("page,P,fill,n_valid", [
    (8, 40, [0, 37], [16, 16]),         # two tiles of the 40 pages' five
    (8, 40, [290, 3], [16, 9]),         # the last tile, padding lanes
    (4, 6, [5, 0], [16, 16]),           # the table is one short tile
    (256, 16, [700, 2100], [16, 16]),   # a tile of 8 pages of 256
])
def test_the_tiled_window_is_the_whole_expansion(cfg, page, P, fill,
                                                 n_valid):
    """The window's fold over tiles of its row's pages, stopped at the last
    real lane's tile, against the parent's form: every page of the slot
    gathered, K and V of all ``P * page`` positions rebuilt, one masked
    attention. The trash page holds NaN and pages past the row's keys are
    never read into a weight."""
    la = cfg.latent
    B, W, H = 2, 16, cfg.heads
    q, pool, bt, wpos, w = window_case(1, B, W, page, P, la, H, fill)
    # a row owns only the pages its lanes can reach; the rest are trash
    reach = (np.asarray(fill) + W - 1) // page + 1
    bt = jnp.where(jnp.arange(P)[None] < jnp.asarray(reach)[:, None], bt, 0)
    n = jnp.asarray(n_valid, jnp.int32)
    got = hybrid._mla_window_call(q, pool, bt, wpos, n, w, la=la)
    rows = pool[bt][:, :, 0].reshape(B, P * page, -1)
    k, v = hybrid._mla_keys_values(rows, w, la)
    want = hybrid._masked_attention(q, k, v, hybrid._causal(wpos, P * page),
                                    jnp.max(wpos))
    real = np.arange(W)[None] < np.asarray(n_valid)[:, None]
    diff = np.abs(np.asarray(got - want))[np.broadcast_to(
        real[:, None, :, None], got.shape)]
    assert np.isfinite(np.asarray(got)).all() and diff.max() < 2e-5


def test_the_window_holds_a_tile_and_its_bound_is_traced(cfg):
    """Under a table 32k wide a window that ends at key 8,999 holds no
    temporary of the slot's length (the widest key axis is a tile), its loop
    is a ``while`` under a traced bound (a ``fori_loop`` between static
    bounds would be a ``scan`` over all 16 tiles), and what lies past its
    keys, NaN here, reaches no weight."""
    la, page, P = cfg.latent, 256, 128
    q, pool, bt, wpos, w = window_case(2, 1, 16, page, P, la, cfg.heads,
                                       [8984])
    pool = pool.at[np.asarray(bt)[0, 40:]].set(jnp.nan)    # keys 10,240 ..
    n = jnp.asarray([16], jnp.int32)
    got = hybrid._mla_window_call(q, pool, bt, wpos, n, w, la=la)
    assert np.isfinite(np.asarray(got)).all()
    text = str(jax.make_jaxpr(
        lambda *a: hybrid._mla_window_call(*a, la=la))(q, pool, bt, wpos, n,
                                                       w))
    assert "32768" not in text and "2048" in text
    assert "while[" in text and "scan[" not in text


# ---- the absorbed kernel at a head count off the tile --------------------------

def latent_case(H, dtype=np.float32, seed=0, page=8, P=6, dk=128, dv=96):
    rng = np.random.default_rng(seed)
    B = 5
    # a slot of six pages is swept a page a step, a wider one four (PR 44):
    # its rows end inside a block, on its edge and at the table's end
    lengths = np.asarray([0, 1, 17, 38, 48] if P == 6
                         else [0, 31, 32, 8 * P, 301], np.int32)
    bt = 1 + rng.permutation(B * P).reshape(B, P).astype(np.int32)
    pool = rng.normal(size=(B * P + 1, 1, page, dk)).astype(dtype)
    q = rng.normal(size=(B, H, dk)).astype(np.float32)
    return q, pool, bt, lengths, dv


@pytest.mark.parametrize("P", [6, 64], ids=["a_page_a_step", "four_a_step"])
@pytest.mark.parametrize("H", [20, 5, 3, 8])
def test_latent_kernel_against_a_float32_oracle_at_any_head_count(H, P):
    q, pool, bt, lengths, dv = latent_case(H, P=P)
    assert pa.latent_block(pool[0].nbytes, P) == (1 if P == 6 else 4)
    got = np.asarray(pa.paged_attention_latent(
        *map(jnp.asarray, (q, pool, bt, lengths)), v_width=dv, scale=0.25,
        interpret=True))
    assert got.shape == (5, H, dv)
    for b, n in enumerate(lengths):
        rows = np.concatenate([pool[p, 0] for p in bt[b]])[:n]
        if not n:
            assert not got[b].any()
            continue
        s = q[b] @ rows.T * 0.25
        p = np.exp(s - s.max(axis=1, keepdims=True))
        want = (p / p.sum(axis=1, keepdims=True)) @ rows[:, :dv]
        assert np.abs(got[b] - want).max() < 2e-5


def test_a_whole_head_count_reaches_the_call_as_it_is(monkeypatch):
    """32 heads (Ling's): ``paged_attention_latent`` hands ``_pa_latent_call``
    the query unpadded and returns its result, bit for bit; 20 heads go in as
    24 and the extra contexts never come out. (That the call itself is the
    PARENT commit's, text and bits, was checked tree against tree when PR 42
    was built: CHANGES.md.)"""
    seen = []
    inner = pa._pa_latent_call

    def spy(q, *args, **kw):
        seen.append(q.shape)
        return inner(q, *args, **kw)

    monkeypatch.setattr(pa, "_pa_latent_call", spy)
    for H, padded in ((32, 32), (20, 24)):
        q, pool, bt, lengths, dv = map(
            lambda a: a if isinstance(a, int) else jnp.asarray(a),
            latent_case(H))
        got = pa.paged_attention_latent(q, pool, bt, lengths, v_width=dv,
                                        scale=0.25, interpret=True)
        direct = inner(jnp.pad(q, ((0, 0), (0, padded - H), (0, 0)))[:, None],
                       pool, bt, lengths, v_width=dv, scale=0.25,
                       interpret=True)[:, 0, :H]
        assert seen[-1] == (5, 1, padded, 128)
        assert np.array_equal(np.asarray(got), np.asarray(direct))


# ---- the sweep in blocks, as the pool counts it -----------------------------

def test_the_pool_counts_the_sweep_by_the_calls_own_rule(cfg):
    """A slot of 64 pages sweeps four a grid step (``latent_block``, the rule
    the call reads its shapes with); a row with nothing to read takes one
    step, as every idle row of the call does; every mla layer's call sweeps
    the same."""
    pool = PagedKVPool(cfg, num_pages=80, page_size=8, slots=8,
                       slot_positions=512)
    layers = cfg.mixers.count("mla")
    sweep, = hybrid.accountants(cfg, hybrid.Geometry(8, 64, True))
    assert (sweep.layers, sweep.block) == (layers, 4)
    # a row at position p attends p + 1 keys; an empty row sweeps one step
    # as an idle one does
    pool.note(sweep.decode([0, 7, 8, 32, 99], rows=8, context=100))
    # pages 1, 1, 2, 5, 13; steps 1, 1, 1, 2, 4 and three idle rows
    assert pool.stats["latent_sweep_pages"] == layers * 22
    assert pool.stats["latent_sweep_steps"] == layers * 12
    pool.note(sweep.decode([511] * 8, rows=8, context=512))
    assert pool.stats["latent_sweep_pages"] == layers * (22 + 8 * 64)
    assert pool.stats["latent_sweep_steps"] == layers * (12 + 8 * 16)


# ---- the routed feed-forward with its shared expert ----------------------------

def test_the_held_layer_is_the_uncut_reference_layer(params, sizes, cfg):
    """``count`` 0 with ``d_shared``: every expert here beside the shared
    one, so the layer's result is the uncut reference's, every pair held."""
    layer = params["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (40, 64)), F32)
    want = REFERENCE.routed_ffn(x, layer, REFERENCE.shape_of(sizes))
    y, counts = moe_topk_held(x, x, layer, cfg.routed, jnp.ones(40, bool),
                              interpret=True)
    by = dict(zip(MOE_STATS, np.asarray(counts)))
    assert by["pairs_routed"] == 80 == by["pairs_held"]
    assert by["pairs_dropped"] == 0 == by["pairs_misplaced"]
    assert np.abs(np.asarray(y - want)).max() < 2e-5
    # and the shared expert is in it: without it the result differs
    bare, _ = moe_topk_held(x, x, {k: v for k, v in layer.items()
                                   if k != "shared"},
                            cfg.routed._replace(d_shared=0),
                            jnp.ones(40, bool), interpret=True)
    assert np.abs(np.asarray(y - bare)).max() > 1e-3


# ---- the engine ----------------------------------------------------------------

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
    """Five requests through three slots; the ticks are labelled by path,
    the windows' keys are counted by tile and as the mathematics needs them,
    and nothing a slot exists to snapshot."""
    decoder = ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                                page_size=8, prefill_chunk=32,
                                paged_attn=impl)
    prompts = [ids[0, :40], ids[1, :71], ids[2, :9], ids[0, 50:120],
               ids[1, 30:63]]
    got = drain(decoder, [decoder.submit(p, 6) for p in prompts])
    for p, g in zip(prompts, got):
        assert list(g) == greedy(params, sizes, p, 6)
    stats = decoder._kv.stats
    latent = "attn_ticks_latent" + ("" if impl == "kernel" else "_window")
    assert stats[latent] == stats["attn_ticks_" + impl] \
        - stats["prefill_chunks"] > 0
    assert ("attn_ticks_latent_window" in stats) == (impl == "gather")
    assert stats["prefill_tokens"] == sum(len(p) for p in prompts)
    assert stats["moe_pairs_dropped"] == 0 == stats["moe_pairs_misplaced"]
    assert stats["moe_pairs_held"] == stats["moe_pairs_routed"] > 0
    # windows of 32 (and a last, shorter one) from offset 0: a tile is the
    # slot's 28 pages of 8 (224 keys, under _KEY_TILE), so every window
    # attends over one tile; context and pairs are the causal sums
    windows = [(off, min(32, len(p) - off))
               for p in prompts for off in range(0, len(p), 32)]
    sweep, = decoder._accountants
    assert sweep.tile == 224
    assert stats["latent_window_keys"] == 224 * len(windows)
    assert stats["latent_window_context"] == sum(o + w for o, w in windows)
    assert stats["latent_window_pairs"] == sum(
        w * o + w * (w + 1) // 2 for o, w in windows)
    assert decoder._kv.snapshot_bytes == 0
    # the absorbed kernel's sweep as the scheduler counts it: a request's
    # five ticks after its first token attend len + 1 .. len + 5 keys in each
    # of the four mla layers (and a tick dispatched before its last token
    # was drained one key more); 28 pages a slot are swept a page a step,
    # every row of a tick at least one step
    pool = decoder._kv
    assert (sweep.layers, sweep.block) == (4, 1)
    def pages(j):
        return 4 * sum(-(-(len(p) + j) // 8) for p in prompts)
    if impl == "kernel":
        want = sum(pages(j) for j in range(1, 6))
        assert want <= stats["latent_sweep_pages"] <= want + pages(6)
        idle = stats["latent_sweep_steps"] - stats["latent_sweep_pages"]
        assert idle % 4 == 0 and 0 < idle <= 4 * 3 * stats["attn_ticks_latent"]
    else:
        assert stats["latent_sweep_pages"] == stats["latent_sweep_steps"] == 0


def test_four_rows_on_one_stored_prefix(params, cfg, sizes, ids):
    """A context of 48 tokens registered (pages alone: no snapshot, no
    restore), then four callers on it at once: the tokens of four private
    copies (the reference decides), the prefix's six pages held by reference
    in all four block tables and never written: bit for bit what they held
    before the four were admitted."""
    tracing._SPAN_LOG.clear()
    dec = ContinuousDecoder(params, cfg, max_slots=4, max_len=224,
                            page_size=8, prefill_chunk=32)
    doc = ids[2, :48]
    first = np.concatenate([doc, ids[0, :11]])
    a = drain(dec, [dec.submit(first, 5, prefix_key="d", prefix_len=48)])[0]
    assert list(a) == greedy(params, sizes, first, 5)
    stored, plen = dec._kv.lookup_prefix(dec._prefix_store["d"][1])
    assert plen == 48 and len(stored) == 6
    before = [np.asarray(layer["kv"])[list(stored)]
              for layer in dec._kv.buffers]
    asks = [np.concatenate([doc, q]) for q in
            (ids[0, 20:31], ids[1, :23], ids[1, 40:49], ids[0, 60:97])]
    reqs = [dec.submit(p, 7, prefix_key="d", prefix_len=48) for p in asks]
    dec.step()
    tables = np.asarray(dec._bt_host)
    assert all(list(tables[s, :6]) == list(stored) for s in range(4))
    assert (dec._kv._refs[list(stored)] == 5).all()     # the store and four
    got = drain(dec, reqs)
    for p, g in zip(asks, got):
        assert list(g) == greedy(params, sizes, p, 7)
    after = [np.asarray(layer["kv"])[list(stored)]
             for layer in dec._kv.buffers]
    assert all(np.array_equal(x, y) for x, y in zip(before, after))
    stats = dec._kv.stats
    assert dec.stats["prefix_hits"] == 4
    assert dec.stats["prefix_hit_tokens"] == 4 * 48 \
        == stats["prefix_tokens_shared"]
    assert stats["prefix_misses"] == 1
    assert not any(k.startswith("state_snapshot") for k in stats)
    names = {row[0] for row in tracing.span_log()}
    assert "continuous.prefill_chunk" in names
    assert not names & {"decoder.state_snapshot", "decoder.state_restore"}


def test_a_prefix_that_ends_inside_a_page_copies_the_boundary(params, cfg,
                                                              sizes, ids):
    """A stored prefix of 45 tokens: five whole pages shared, the sixth
    copied (the hit's window writes into it), the tokens the reference's."""
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=224,
                            page_size=8, prefill_chunk=32)
    doc = ids[2, :45]
    first = np.concatenate([doc, ids[0, :11]])
    second = np.concatenate([doc, ids[1, :23]])
    drain(dec, [dec.submit(first, 3, prefix_key="d", prefix_len=45)])
    b = drain(dec, [dec.submit(second, 5, prefix_key="d", prefix_len=45)])[0]
    assert list(b) == greedy(params, sizes, second, 5)
    assert dec._kv.stats["prefix_tokens_shared"] == 40
    assert dec.stats["prefix_hit_tokens"] == 45
    # a whole-prompt hit answers from the boundary's stored logits
    c = drain(dec, [dec.submit(doc, 4, prefix_key="d", prefix_len=45)])[0]
    assert list(c) == greedy(params, sizes, doc, 4)


def test_defragmentation_moves_shared_pages_under_their_readers(params, cfg,
                                                                sizes, ids):
    """Long requests retire under a stored prefix and a live reader of it;
    compaction permutes the latent pages, the store's and the reader's alike:
    the reader's tokens are still the reference's, and so are a later
    hit's."""
    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                            page_size=8, prefill_chunk=32, kv_pages=60,
                            defrag_threshold=1)
    doc = ids[2, :48]
    reqs = [dec.submit(ids[0, :90], 2), dec.submit(ids[1, :90], 2),
            dec.submit(np.concatenate([doc, ids[0, :9]]), 30,
                       prefix_key="d", prefix_len=48)]
    got = drain(dec, reqs)
    assert list(got[2]) == greedy(params, sizes,
                                  np.concatenate([doc, ids[0, :9]]), 30)
    assert dec._kv.stats["defrag_moves"] > 0
    late = np.concatenate([doc, ids[1, 5:20]])
    assert list(drain(dec, [dec.submit(late, 4, prefix_key="d",
                                       prefix_len=48)])[0]) \
        == greedy(params, sizes, late, 4)


def test_pool_shapes_are_pages_and_nothing_a_slot(cfg):
    pool = PagedKVPool(cfg, num_pages=9, page_size=8, residency=False,
                       slots=2, slot_positions=64)
    assert [sorted(layer) for layer in pool.buffers] == [["kv"]] * 4
    assert pool.buffers[0]["kv"].shape == (9, 1, 8, 128)
    assert pool.snapshot_bytes == 0
    assert pool.bytes_per_position() == 4 * 128 * 4


@pytest.mark.parametrize("kwargs,reason", [
    (dict(kv_dtype="int8"), "an mla layer's latent pages"),
    (dict(draft_params={}, draft_cfg=None), "a draft model"),
    (dict(mesh="a mesh"), "a mesh"),
])
def test_refused_combinations_say_why(params, cfg, kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        ContinuousDecoder(params, cfg, max_slots=2, max_len=64, **kwargs)


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    low = cfg._replace(dtype=jnp.bfloat16)
    got = program_logits(params, ids[:1], low)
    assert np.abs(got - want[:1]).max() > 20 * TOL
