"""The hybrid decoder (MiniCPM-SALA's shape: lightning linear-attention state
beside block-sparse attention pages) against its plain reference, at tiny
widths: hidden 64, 4 query / 2 KV heads of 16, layers ``minicpm4, lightning,
lightning, minicpm4``, ``dense_len`` 64, blocks of 8, ``topk`` 6, window 16,
so that contexts of 100-200 tokens really drop blocks.

Tolerances. Everything here is float32 on the CPU, where a matrix product is
exact to rounding, so the program and the reference differ by the order of
their sums alone: logits of scale 0.04-0.2 agree to ~2e-7, and ``TOL`` 2e-5
leaves two orders of magnitude. A bfloat16 run of the program misses it by
two more (the last test), so computing in a lower precision than stated
cannot pass.
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
    DeltaRule, LatentAttention, RoutedExperts, ShortConv, SparseAttention,
    StateSpace, TransformerConfig, decode_step_paged, decode_window_paged,
    generate, generate_cached, init_paged_cache, init_transformer,
    transformer_apply)
from mmlspark_tpu.ops.lightning_attention import lightning_decode_step
from mmlspark_tpu.ops.paged_attention import paged_attention_selected
from mmlspark_tpu.serving.continuous import ContinuousDecoder
from mmlspark_tpu.serving.kv_pool import PagedKVPool

TOL = 2e-5
VOCAB = 97
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6,
              window_size=16, init_blocks=1, dense_len=64)
REFERENCE = bench_run.load_by_path("references", "minicpm_sala")
DRIVER = bench_run.load_by_path("drivers", "generate_docs")


def tiny_sizes():
    """The benchmark's configuration file with its widths shrunk: every key
    the reference and the driver's mapping read is the real file's."""
    with open(os.path.join(bench_run.HERE, "configs",
                           "minicpm_sala_l8.json")) as fh:
        config = json.load(fh)
    return dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, lightning_nh=4, lightning_nkv=4,
        lightning_head_dim=16, vocab_size=VOCAB, num_hidden_layers=4,
        dim_model_base=16, sparse_config=SPARSE, compute_dtype="float32",
        param_dtype="float32",
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"])


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
    return np.random.default_rng(0).integers(1, VOCAB, (3, 200)).astype(
        np.int32)


@pytest.fixture(scope="module")
def want(sizes, params, ids):
    """The reference's logits at every position of every sequence."""
    return np.stack([np.asarray(REFERENCE.logits(
        params, sizes, row, np.arange(row.size))) for row in ids])


def program_logits(params, ids, cfg):
    hidden = transformer_apply(params, jnp.asarray(ids), cfg)
    return np.asarray(hidden.astype(jnp.float32) @ params["lm_head"]["w"])


def test_mapping_keeps_the_published_numbers(sizes, cfg):
    assert cfg.mixers == ("sparse", "lightning", "lightning", "sparse")
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    assert cfg.embed_scale == 12 and cfg.logit_scale == 16 / 64
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg.sparse == SparseAttention(**SPARSE)


def test_full_forward_matches_the_reference(params, ids, cfg, want):
    assert np.abs(program_logits(params, ids, cfg) - want).max() < TOL


@functools.lru_cache(maxsize=None)
def paged_programs(cfg, impl, page, length):
    """The chunk window and the decode tick, jitted once a configuration
    (an interpreted Pallas kernel is slow to trace, not to run)."""
    def window(params, tok, off, bufs, bt_row, slot, n):
        return decode_window_paged(
            params, tok, off, bufs, bt_row, cfg, page_size=page,
            length=length, impl=impl, n_valid=n, slot=slot, last_only=True)

    def tick(params, tok, pos, bufs, bt, active):
        return decode_step_paged(params, tok, pos, bufs, bt, cfg,
                                 page_size=page, length=length,
                                 active=active, impl=impl)
    return jax.jit(window), jax.jit(tick)


def paged_run(params, ids, cfg, impl, prompt_lens, steps, chunk=32, page=8):
    """Chunked prefill of each row through the pool (padded windows, starts
    off every boundary), then ``steps`` decode ticks of all rows at once,
    teacher-forced from ``ids``. Returns (prefill logits, tick logits)."""
    slots, L = len(prompt_lens), 208
    per = L // page
    window, tick = paged_programs(cfg, impl, page, L)
    bufs = hybrid.init_hybrid_pool(cfg, 1 + slots * per, page, slots, L)
    bt = jnp.asarray(1 + np.random.default_rng(3).permutation(
        slots * per).reshape(slots, per), jnp.int32)
    first = []
    for s, n in enumerate(prompt_lens):
        off = 0
        while off < n:
            w = min(chunk, n - off)
            tok = np.zeros((1, chunk), np.int32)
            tok[0, :w] = ids[s, off:off + w]
            last, bufs = window(params, jnp.asarray(tok), jnp.asarray([off]),
                                bufs, bt[s:s + 1], jnp.int32(s),
                                jnp.asarray([w]))
            off += w
        first.append(np.asarray(last[0]))
    pos = np.asarray(prompt_lens)
    ticks = []
    for i in range(steps):
        tok = jnp.asarray([ids[s, n + i] for s, n in enumerate(prompt_lens)])
        # one row sits a few ticks out: an idle row's state must stay put
        active = jnp.asarray([True, not 3 <= i < 6, True][:slots])
        logits, bufs = tick(params, tok, jnp.asarray(pos + i), bufs, bt,
                            active)
        ticks.append((np.asarray(active), np.asarray(logits)))
    return first, ticks


@pytest.mark.parametrize("impl", ["kernel", "gather"])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        params, ids, cfg, want, impl):
    """Prompts of 100, 50 and 77 tokens (one still under ``dense_len``, so
    the tick mixes dense rows with sparse ones), then 40 ticks, every row
    past ``dense_len`` at the end."""
    lens = [100, 50, 77]
    first, ticks = paged_run(params, ids, cfg, impl, lens, 40)
    for s, n in enumerate(lens):
        assert np.abs(first[s] - want[s, n - 1]).max() < TOL
    # row 1 idles over ticks 3-5 and so falls three positions behind
    for i, (active, logits) in enumerate(ticks):
        for s, n in enumerate(lens):
            if active[s] and not (s == 1 and i >= 3):
                assert np.abs(logits[s] - want[s, n + i]).max() < TOL, (i, s)


def test_an_idle_row_keeps_its_state_and_its_pages(params, ids, cfg):
    _, ticks = paged_run(params, ids, cfg, "kernel", [100, 50, 77], 8)
    again = paged_run(params, ids, cfg, "kernel", [100, 50, 77], 3)[1]
    # ticks 0-2 are the same work; after idling 3-5, row 1 at tick 6 gets the
    # token of tick 6 at position 56, not a state three ticks stale
    assert np.array_equal(ticks[2][1], again[2][1])
    assert np.isfinite(ticks[7][1]).all()


def test_serving_layout_relays_qkv_of_lightning_and_sparse_alone(cfg):
    """Object for object the caller's tree, but ``q``, ``k``, ``v`` of a
    lightning or a sparse layer, held as ``{"wt": w.T}``; a model with
    neither kind gets its own tree back."""
    every = cfg._replace(
        layers=len(hybrid.MIXERS), mixers=hybrid.MIXERS, max_len=48,
        kda=DeltaRule(conv_kernel=3), conv=ShortConv(taps=3),
        latent=LatentAttention(latent=32, nope=16, rope=8, value=16),
        ssm=StateSpace(heads=4, head_dim=16, state=16, groups=2, taps=3,
                       chunk=8))
    params = hybrid.init_hybrid(every, 2)
    before = jax.tree.map(np.copy, params)
    got = hybrid.serving_layout(every, params)
    assert {k: got[k] is params[k] for k in params} == dict(
        {k: True for k in params}, layers=False)
    for kind, lp, sl in zip(every.mixers, params["layers"], got["layers"]):
        relaid = {"q", "k", "v"} if kind in ("lightning", "sparse") else set()
        assert (sl is lp) == (not relaid) and set(sl) == set(lp)
        for name in lp:
            if name in relaid:
                assert set(sl[name]) == {"wt"}
                assert np.array_equal(sl[name]["wt"], lp[name]["w"].T)
            else:
                assert sl[name] is lp[name], (kind, name)
    jax.tree.map(np.testing.assert_array_equal, params, before)
    rest = tuple(k for k in hybrid.MIXERS if k not in ("lightning", "sparse"))
    plain = every._replace(layers=len(rest), mixers=rest)
    tree = hybrid.init_hybrid(plain, 2)
    assert hybrid.serving_layout(plain, tree) is tree
    # shapes alone will do
    shapes = jax.eval_shape(lambda: hybrid.serving_layout(
        every, jax.tree.map(jnp.asarray, params)))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, got)


@pytest.mark.parametrize("path", ["contiguous", "kernel", "gather"])
def test_the_served_layout_is_the_same_product(params, ids, cfg, want, path):
    """The tree a decoder serves from (``hybrid.serving_layout``) through
    the full forward, and through chunked prefill and decode under both
    paged forms, against the caller's tree through the same path (the
    parent's formulation) and the reference."""
    served = hybrid.serving_layout(cfg, params)
    if path == "contiguous":
        got = program_logits(served, ids, cfg)
        assert np.abs(got - program_logits(params, ids, cfg)).max() < TOL
        assert np.abs(got - want).max() < TOL
        return
    lens = [100, 50, 77]
    first, ticks = paged_run(served, ids, cfg, path, lens, 12)
    first0, ticks0 = paged_run(params, ids, cfg, path, lens, 12)
    for s, n in enumerate(lens):
        assert np.abs(first[s] - first0[s]).max() < TOL
        assert np.abs(first[s] - want[s, n - 1]).max() < TOL
    for i, ((active, logits), (_, logits0)) in enumerate(zip(ticks, ticks0)):
        assert np.abs(logits - logits0)[active].max() < TOL, i
        for s, n in enumerate(lens):
            if active[s] and not (s == 1 and i >= 3):
                assert np.abs(logits[s] - want[s, n + i]).max() < TOL, (i, s)


@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_lightning_form_is_the_scan(chunk):
    """Two chunk sizes against the recurrence position by position, with a
    padded last window and a state carried across windows."""
    rng = np.random.default_rng(1)
    B, H, S, d = 2, 4, 50, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, d)), jnp.float32)
               for _ in range(3))
    lam = np.exp(-np.asarray(hybrid.lightning_rates(H)))
    state = np.zeros((B, H, d, d))
    want = np.zeros((B, H, S, d))
    for t in range(S):
        state = lam[None, :, None, None] * state + np.einsum(
            "bhd,bhe->bhde", np.asarray(k[:, :, t]), np.asarray(v[:, :, t]))
        want[:, :, t] = np.einsum("bhd,bhde->bhe", np.asarray(q[:, :, t]),
                                  state) / np.sqrt(d)
    carried = jnp.zeros((B, H, d, d), jnp.float32)
    got = []
    for off in range(0, S, chunk):
        n = min(chunk, S - off)

        def window(t):
            return jnp.pad(t[:, :, off:off + n],
                           ((0, 0), (0, 0), (0, chunk - n), (0, 0)),
                           constant_values=7.0)      # padding must not count
        o, carried = hybrid.lightning_chunk(
            window(q), window(k), window(v), carried,
            jnp.full((B,), n, jnp.int32))
        got.append(np.asarray(o[:, :, :n]))
    assert np.abs(np.concatenate(got, axis=2) - want).max() < 1e-4
    assert np.abs(np.asarray(carried) - state).max() < 1e-4


def test_lightning_decode_kernel_is_the_recurrence():
    rng = np.random.default_rng(2)
    B, H, d = 3, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
               for _ in range(3))
    state = jnp.asarray(rng.normal(size=(B, H, d, d)), jnp.float32)
    active = jnp.asarray([True, False, True])
    o, new = lightning_decode_step(q, k, v, state, active)
    want_o, want_new = hybrid.lightning_chunk(
        q[:, :, None], k[:, :, None], v[:, :, None], state,
        active.astype(jnp.int32))
    assert np.abs(np.asarray(new) - np.asarray(want_new)).max() < 1e-5
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))
    assert np.abs(np.asarray(o) - np.asarray(want_o[:, :, 0]))[[0, 2]].max() \
        < 1e-5


def test_selected_kernel_attends_the_listed_pages_only():
    rng = np.random.default_rng(0)
    B, G, hg, hd, page, N, P = 3, 2, 2, 16, 8, 20, 6
    q = jnp.asarray(rng.normal(size=(B, G, hg, hd)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(N, G, page, 2 * hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, N))[:B * P].reshape(B, P),
                     jnp.int32)
    sel = np.asarray([[[0, 2, -1, 4], [1, -1, 3, 5]],
                      [[5, 4, -1, -1], [0, 1, 2, 3]],
                      [[-1, -1, -1, -1], [2, -1, -1, -1]]], np.int32)
    lens = np.asarray([37, 48, 20], np.int32)
    got = np.asarray(paged_attention_selected(q, kv, bt, jnp.asarray(sel),
                                              jnp.asarray(lens)))
    want = np.zeros_like(got)
    for b in range(B):
        for g in range(G):
            rows = [np.asarray(kv[bt[b, lp], g])[o]
                    for lp in sel[b, g] if lp >= 0
                    for o in range(page) if lp * page + o < lens[b]]
            if rows:
                rows = np.stack(rows)
                s = np.asarray(q[b, g]) @ rows[:, :hd].T / np.sqrt(hd)
                p = np.exp(s - s.max(-1, keepdims=True))
                want[b, g] = p / p.sum(-1, keepdims=True) @ rows[:, hd:]
    assert np.abs(got - want).max() < 1e-5
    assert not got[2, 0].any()              # nothing listed: zeros


def test_selected_blocks_are_the_references():
    """float32 on both sides: the program's scorer and the reference's pick
    the same set of blocks for every query past ``dense_len``."""
    rng = np.random.default_rng(4)
    sp = SparseAttention(**SPARSE)
    Hq, G, S, d = 4, 2, 200, 16
    q = jnp.asarray(rng.normal(size=(Hq, S, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(G, S, d)), jnp.float32)
    t = np.arange(SPARSE["dense_len"], S)
    want = np.asarray(REFERENCE.selected_blocks(q[:, t], k, jnp.asarray(t),
                                                SPARSE))          # (G, Q, nb)
    s, ks = sp.kernel_stride, sp.kernel_size
    ends = s * np.arange(S // s) + s - 1
    ck = np.stack([np.asarray(k[:, e - ks + 1:e + 1]).mean(axis=1)
                   if e >= ks - 1 else np.zeros((G, d)) for e in ends], axis=1)
    idx, ok = hybrid.sparse_select(q[None, :, t], jnp.asarray(ck)[None],
                                   jnp.asarray(t)[None], sp)
    idx, ok = np.asarray(idx[0]), np.asarray(ok[0])
    assert ok.all() and want.sum(axis=-1).max() == sp.topk
    for g in range(G):
        for i in range(t.size):
            assert set(idx[g, i]) == set(np.flatnonzero(want[g, i])), (g, i)
    # and selection drops blocks: the last query has 25 to choose from
    assert want.shape[-1] == 25


def test_generate_cached_is_the_full_forward(params, ids, cfg):
    prompt = jnp.asarray(ids[:2, :70])
    assert np.array_equal(
        np.asarray(generate_cached(params, prompt, cfg, max_new_tokens=10)),
        np.asarray(generate(params, prompt, cfg, max_new_tokens=10)))


@pytest.fixture(scope="module")
def decoder(params, cfg):
    return ContinuousDecoder(params, cfg, max_slots=3, max_len=224,
                             page_size=8, prefill_chunk=32)


@pytest.mark.parametrize("max_len,dense_page",
                         [(224, 16), (1024, 64), (32768, 256)])
def test_a_sparse_model_is_served_in_pages_of_its_sparse_block(
        params, cfg, max_len, dense_page):
    """No ``page_size`` given: selection and the compressed keys are laid
    out by the sparse block, so the page stays there whatever ``max_len``
    would derive; the layer type decides, not a name."""
    from mmlspark_tpu.serving.continuous import derived_page_size
    assert derived_page_size(cfg, max_len) == SPARSE["block_size"]
    assert derived_page_size(cfg._replace(mixers=("lightning",) * 4),
                             max_len) == dense_page
    if max_len == 224:
        dec = ContinuousDecoder(params, cfg, max_slots=1, max_len=max_len,
                                prefill_chunk=32)
        assert dec._kv.stats["page_size"] == SPARSE["block_size"]
        assert dec._kv.stats["pages_per_slot"] == 224 // 8


def drain(decoder, reqs):
    while not all(r.done for r in reqs):
        decoder.step()
    return reqs


def greedy(params, cfg, prompt, n):
    out = generate_cached(params, jnp.asarray(prompt)[None], cfg,
                          max_new_tokens=n)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def test_decoder_equals_generate_cached_with_slots_reused(decoder, params,
                                                          cfg):
    """Seven requests over three slots, lengths on both sides of
    ``dense_len``: a slot's second tenant would show a state left behind."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (100, 30, 77, 150, 9, 64, 120)]
    reqs = drain(decoder, [decoder.submit(p, 12) for p in prompts])
    for p, r in zip(prompts, reqs):
        assert r.error is None and r.tokens == greedy(params, cfg, p, 12)
    stats = decoder._kv.stats
    assert stats["attn_ticks_sparse"] > 0 and stats["attn_ticks_dense"] > 0
    assert stats["attn_ticks_gather"] == 0


@pytest.fixture(scope="module")
def document():
    return np.random.default_rng(9).integers(1, VOCAB, 100).astype(np.int32)


def ask(decoder, document, question, **kw):
    return drain(decoder, [decoder.submit(
        np.concatenate([document, question]), 8, prefix_key="doc", **kw)])[0]


def test_prefix_hit_equals_the_whole_prefill(decoder, params, cfg, document):
    """A miss registers pages and snapshot at ``prefix_len`` 100 (not a page
    boundary: the boundary page is copied); hits share the pages, restore
    the snapshot and prefill the question alone. Tokens and the first
    token's logits equal the same prompt prefilled whole."""
    rng = np.random.default_rng(6)
    before = dict(decoder._kv.stats)
    questions = [rng.integers(1, VOCAB, n).astype(np.int32)
                 for n in (20, 41, 20)]
    reqs = [ask(decoder, document, q, prefix_len=100) for q in questions]
    for q, r in zip(questions, reqs):
        assert r.error is None
        assert r.tokens == greedy(params, cfg,
                                  np.concatenate([document, q]), 8)
    whole = ask(decoder, document, np.zeros(0, np.int32))
    assert whole.tokens == greedy(params, cfg, document, 8)
    moved = {k: decoder._kv.stats.get(k, 0) - before.get(k, 0)
             for k in ("state_snapshots_stored", "state_snapshots_restored",
                       "state_snapshot_bytes_restored", "prefix_share_hits")}
    assert moved["state_snapshots_stored"] == 1
    assert moved["state_snapshots_restored"] == 3
    # two lightning states (4 heads of 16 x 16 float32) and two rows of
    # compressed keys (2 KV heads x 224 / 2 entries of 16 float32)
    assert moved["state_snapshot_bytes_restored"] \
        == 3 * decoder._kv.snapshot_bytes \
        == 3 * 2 * (4 * 16 * 16 * 4 + 2 * 112 * 16 * 4)
    assert moved["prefix_share_hits"] == 3 * (100 // 8)
    assert decoder._kv.stats["prefix_misses"] >= 1


def test_prefix_hit_logits_equal_whole_prefill(params, cfg, document, want,
                                               sizes):
    """Logits, not tokens: the hit's first-token logits against the
    reference's full forward over document + question."""
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=224,
                            page_size=8, prefill_chunk=32)
    seen = []
    insert = dec._insert_chunk_locked
    dec._insert_chunk_locked = lambda group, logits, *rest: (
        seen.append(np.asarray(logits)), insert(group, logits, *rest))[1]
    q = np.random.default_rng(7).integers(1, VOCAB, 33).astype(np.int32)
    full = np.concatenate([document, q])
    for _ in range(2):                      # a miss, then a hit
        drain(dec, [dec.submit(full, 2, prefix_key="d", prefix_len=100)])
    ref = np.asarray(REFERENCE.logits(params, sizes, full, [full.size - 1]))
    assert dec.stats["prefix_hits"] == 1 and len(seen) == 2
    # the decoder serves the re-laid tree: beside the reference, the
    # caller's own tree through the full forward
    whole = program_logits(params, full[None], cfg)[0, -1]
    assert dec.stats["serving_layout_bytes"] > 0
    for got in seen:
        assert np.abs(got[0] - ref[0]).max() < TOL
        assert np.abs(got[0] - whole).max() < TOL


def _routed_model():
    cfg = TransformerConfig(
        vocab=VOCAB, layers=2, d_model=64, heads=4, d_ff=128, max_len=48,
        causal=True, dtype=jnp.float32, norm="rmsnorm", position="rope",
        mixers=("kda", "mla"), ffn=("dense", "moe"), head_dim=16,
        kda=DeltaRule(conv_kernel=3),
        latent=LatentAttention(latent=32, nope=16, rope=8, value=16),
        routed=RoutedExperts(experts=8, per_token=2, d_expert=32,
                             d_shared=32))
    return hybrid.init_hybrid(cfg, 1), cfg


def _dense_model():
    cfg = TransformerConfig(vocab=VOCAB, layers=1, d_model=32, heads=2,
                            d_ff=64, causal=True, dtype=jnp.float32)
    return init_transformer(cfg), cfg


@pytest.mark.parametrize("model", ["hybrid", "routed", "dense"])
def test_a_decoder_counts_what_it_holds_in_another_layout(params, cfg, model):
    """``stats["serving_layout_bytes"]``: the re-laid leaves' bytes (float32
    here: a sparse layer's q 64 x 64 and k, v 64 x 32, a lightning layer's
    three 64 x 64, two layers each), held INSTEAD of the caller's, whose
    tree stays as it was handed in; 0 where no kind declares a layout."""
    tree, c = {"hybrid": lambda: (params, cfg), "routed": _routed_model,
               "dense": _dense_model}[model]()
    leaves, shape = jax.tree.flatten(tree)
    dec = ContinuousDecoder(tree, c, max_slots=2, max_len=48, page_size=8,
                            prefill_chunk=16)
    after, shape_after = jax.tree.flatten(tree)
    assert shape_after == shape
    assert all(a is b for a, b in zip(after, leaves, strict=True))
    if model != "hybrid":
        assert dec.stats["serving_layout_bytes"] == 0
        return
    relaid = [lp[name]["w"] for lp in tree["layers"] for name in "qkv"]
    assert dec.stats["serving_layout_bytes"] \
        == sum(w.nbytes for w in relaid) \
        == 2 * 4 * (64 * 64 + 2 * 64 * 32) + 2 * 4 * 3 * 64 * 64
    held = [lp[name] for lp in dec._params["layers"] for name in "qkv"]
    assert [set(p) for p in held] == [{"wt"}] * len(held)
    assert [p["wt"].shape for p in held] == [w.shape[::-1] for w in relaid]


def test_a_shorter_prefix_len_is_refused_alone(decoder, document):
    ask(decoder, document, np.ones(5, np.int32), prefix_len=100)
    short = ask(decoder, document, np.ones(5, np.int32), prefix_len=50)
    assert isinstance(short.error, ValueError)
    assert "cannot shorten" in str(short.error)
    fine = ask(decoder, document, np.ones(5, np.int32), prefix_len=100)
    assert fine.error is None and len(fine.tokens) == 8


def test_snapshot_is_evicted_with_its_prefix(params, cfg):
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=224,
                            page_size=8, prefill_chunk=32,
                            prefix_cache_size=1)
    rng = np.random.default_rng(8)
    for key in ("a", "b"):
        drain(dec, [dec.submit(rng.integers(1, VOCAB, 40).astype(np.int32),
                               2, prefix_key=key)])
    assert dec._kv.stats["state_snapshots_stored"] == 2
    assert dec._kv.stats["state_snapshots_evicted"] == 1
    assert list(dec._prefix_store) == ["b"] and len(dec._kv._snapshots) == 1


def test_pool_counts_pages_states_and_snapshots(cfg):
    pool = PagedKVPool(cfg, num_pages=11, page_size=8, residency=False,
                       slots=3, slot_positions=64, max_snapshots=2)
    pages = 2 * 11 * 2 * 8 * 2 * 16 * 4                  # K, V: 2 layers
    rows = 2 * (4 * 16 * 16 * 4 + 2 * 32 * 16 * 4)       # a slot's state, ck
    assert pool.snapshot_bytes == rows
    assert pool.device_bytes() == pages + 3 * rows + 2 * rows
    assert [sorted(c) for c in pool.buffers] == [
        ["ck", "kv"], ["state"], ["state"], ["ck", "kv"]]
    assert pool.bytes_per_position() == 2 * 2 * 2 * 16 * 4
    with pytest.raises(ValueError, match="page_size"):
        PagedKVPool(cfg, num_pages=11, page_size=3, residency=False, slots=3,
                    slot_positions=64)


# ---- the selected-block walk, as the pool counts it (PR 46) -----------------
# tiny widths: blocks and pages of 8, topk 6, dense_len 64 (8 blocks), 2 KV
# heads, 2 sparse layers, a page's slice 8 x 32 float32. (positions of the live
# rows, rows of the call) -> (pages listed a KV head a layer, entries of the
# list walked, entries a grid step)
WALKS = {
    # every row past dense_len with six blocks and more: whole lists of six
    # pages, two a step
    "whole_lists_past_dense_len": (([100, 150, 77], 3), (18, 6, 2)),
    # a row of three blocks lists three: its second block folds page by page
    "a_short_row_lists_its_blocks": (([100, 20], 2), (6 + 3, 6, 2)),
    # two idle rows walk their empty lists
    "idle_rows_take_their_steps": (([100], 3), (6, 6, 2)),
    # a row under dense_len with eight blocks, more than the six a top-k
    # walk lists: the call walks the dense lists, eight entries one step
    "a_long_dense_row_widens_the_walk": (([63, 100], 2), (8 + 6, 8, 8)),
    "at_dense_len_the_row_is_sparse": (([64, 100], 2), (6 + 6, 6, 2)),
}


@pytest.mark.parametrize("name", list(WALKS))
def test_the_pool_counts_the_walk_by_the_calls_own_rule(cfg, name):
    """``select_walk_pages`` / ``select_walk_steps`` from the rows' positions
    as the scheduler holds them: the pages the lists hold, and the grid
    ``(rows, KV heads, entries / select_block)`` of every sparse layer's
    call. Whole lists read ``k``: pages over steps."""
    (positions, rows), (pages, n_sel, k) = WALKS[name]
    pool = PagedKVPool(cfg, num_pages=11, page_size=8, residency=False,
                       slots=3, slot_positions=256)
    walk, = hybrid.accountants(cfg, hybrid.Geometry(8, 32, True))
    assert (walk.layers, walk.walks, walk.steps) == (2, (6, 8), (3, 1))
    pool.note(walk.decode(positions, rows, context=max(positions) + 1))
    stats = pool.stats
    assert stats["select_walk_pages"] == 2 * 2 * pages
    assert stats["select_walk_steps"] == 2 * 2 * rows * (n_sel // k)
    if name == "whole_lists_past_dense_len":
        assert stats["select_walk_pages"] / stats["select_walk_steps"] == k


def test_the_pools_walks_are_the_lists_the_tick_hands_the_kernel(
        cfg, monkeypatch):
    """The sparse kind's accountant and ``select_block`` against what
    ``_selected_decode`` traces: both branches of its ``lax.cond``, the
    top-k walk and the dense walk, reach the launch with the pool's numbers."""
    import mmlspark_tpu.ops.paged_attention as pa
    seen = set()
    inner = pa._select_launch

    def spy(q, kv, bt, sel, lengths, *, k, **kw):
        seen.add((sel.shape[1], k))
        return inner(q, kv, bt, sel, lengths, k=k, **kw)

    monkeypatch.setattr(pa, "_select_launch", spy)
    pa._pa_select_call.clear_cache()
    B, P, page, K = 3, 32, 8, 6
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    jax.eval_shape(
        lambda q, kv, bt, pos, n, idx, ok: hybrid._selected_decode(
            q, kv, bt, pos, n, idx, ok, cfg, page),
        f32((B, 4, 1, 16)), f32((1 + B * P, 2, page, 32)), i32((B, P)),
        i32((B,)), i32((B,)), i32((B, 2, 1, K)),
        jax.ShapeDtypeStruct((B, 2, 1, K), bool))
    pa._pa_select_call.clear_cache()
    walks = hybrid.accountants(cfg, hybrid.Geometry(page, P, True))[0].walks
    assert walks == (6, 8)
    assert seen == {(n, pa.select_block(page * 32 * 4, n)) for n in walks}


def test_compaction_moves_pages_and_leaves_states(decoder, params, cfg):
    """Defragmentation permutes pages and compressed keys together and never
    a state row: decoding goes on token for token."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (90, 20, 100)]
    decoder._defrag_thr = 1
    before = decoder._kv.stats["defrag_moves"]
    reqs = [decoder.submit(p, m) for p, m in zip(prompts, (3, 30, 30))]
    drain(decoder, reqs)
    decoder._defrag_thr = 10 ** 6
    assert decoder._kv.stats["defrag_moves"] > before
    for p, r, m in zip(prompts, reqs, (3, 30, 30)):
        assert r.tokens == greedy(params, cfg, p, m)


@pytest.mark.parametrize("kwargs,reason", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(mesh="a mesh"), "a mesh"),
    (dict(draft_params={}, draft_cfg=TransformerConfig(causal=True)),
     "a draft model"),
])
def test_refused_combinations_say_why(params, cfg, kwargs, reason):
    with pytest.raises(ValueError, match=reason):
        ContinuousDecoder(params, cfg, max_slots=2, max_len=64, page_size=8,
                          **kwargs)


def test_session_export_and_adopt_are_refused(decoder, params, cfg):
    req = decoder.submit(np.arange(1, 20, dtype=np.int32), 30)
    for _ in range(6):
        decoder.step()
    with pytest.raises(ValueError, match="export"):
        decoder.checkpoint_session(req)
    cold = decoder.checkpoint_session(req, export_kv=False)
    assert cold["kv"] is None and cold["session"]["emitted"]
    with pytest.raises(ValueError, match="warm adopt"):
        decoder.restore_session(cold["session"], kv_blob={"length": 1})
    with pytest.raises(ValueError, match="hybrid"):
        decoder._kv.export_session([1], length=1)
    drain(decoder, [req])
    # restored cold it ends where the uninterrupted run does
    again = decoder.restore_session(cold["session"])
    drain(decoder, [again])
    assert decoder.session_result(again) == req.tokens


@pytest.mark.parametrize("change,message", [
    (dict(mixers=("sparse", "lightning")), "mixers for"),
    (dict(mixers=("sparse", "window", "lightning", "sparse")), "unknown"),
    (dict(sparse=None), "need cfg.sparse"),
    (dict(kv_heads=3), "kv_heads"),
    (dict(causal=False), "causal"),
    (dict(sparse=SparseAttention(**dict(SPARSE, topk=2))), "more than topk"),
    (dict(sparse=SparseAttention(**dict(SPARSE, kernel_size=5))),
     "multiples of kernel_stride"),
])
def test_config_is_checked(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        hybrid.check_config(cfg._replace(**change))


def test_entry_points_that_cannot_hold_a_state_refuse(params, cfg, ids):
    from mmlspark_tpu.models.zoo.transformer import generate_beam
    with pytest.raises(ValueError, match="generate_beam"):
        generate_beam(params, jnp.asarray(ids[:1, :8]), cfg)
    with pytest.raises(ValueError, match="init_hybrid_pool"):
        init_paged_cache(cfg, 4, 8)
    dense = TransformerConfig(vocab=VOCAB, layers=1, d_model=32, heads=2,
                              d_ff=64, causal=True, dtype=jnp.float32)
    with pytest.raises(ValueError, match="hybrid"):
        decode_window_paged(
            jax.tree.map(jnp.asarray, init_transformer(dense)),
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            init_paged_cache(dense, 4, 8), jnp.zeros((1, 2), jnp.int32),
            dense, page_size=8, length=16, last_only=True)


def test_bfloat16_misses_the_float32_tolerance(params, ids, cfg, want):
    """The same program computing in bfloat16 fails ``TOL`` by orders of
    magnitude: the comparisons above would catch a lower precision."""
    low = cfg._replace(dtype=jnp.bfloat16)
    assert np.abs(program_logits(params, ids[:1], low) - want[:1]).max() \
        > 50 * TOL
