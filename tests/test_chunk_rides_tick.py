"""A prefill chunk rides the decode tick in the hybrid block: ONE layer walk
(``hybrid.tick_with_window``) over the tick's rows and one slot's window, one
read of the feed-forward weights and the head for both, in place of the chunk
program and then the tick.

Model level: the fused walk against ``_extend`` then ``tick`` on the three
hybrid layer mixes (conv + gqa + routed, kda + mla + routed, lightning +
sparse) at ``tests/test_*_decoder.py``'s tiny sizes, a full window bucket and
one shorter than the chunk with padding lanes.

Tolerances. Everything is float32 on the CPU. The tick's tokens are equal and
every buffer the window alone writes, or that holds no product of a joined
matrix (the conv tails of layer 0), is equal BIT FOR BIT. The rest is held to
``TOL`` 2e-5 and not to the bit, for one reason: a feed-forward, a routed
layer's placement products and the head now multiply ``S + W`` rows where they
multiplied ``S`` and ``W``, and XLA:CPU picks a product's summation order by
its shape (PERF.md section 7), so a row's sums differ in their last place,
1e-7 of logits of 0.1-0.6. Routing is dropless and per token: the same experts
are chosen for every row (the counts say so).

Scheduler level: which steps ride, what the pool counts, and that requests
still get the offline generator's tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_hybrid_decoder
import test_lfm2_decoder
import test_ling_decoder
from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo.transformer import (
    TransformerConfig, decode_step_paged, decode_window_paged,
    generate_cached, init_transformer)
from mmlspark_tpu.parallel.moe import MOE_STATS
from mmlspark_tpu.serving import continuous
from mmlspark_tpu.serving.continuous import ContinuousDecoder
from test_ling_decoder import drain

TOL = 2e-5
PAGE, LEN, CHUNK = 8, 160, 32
MIXES = {"conv_gqa_moe": test_lfm2_decoder,
         "kda_mla_moe": test_ling_decoder,
         "lightning_sparse": test_hybrid_decoder}


@pytest.fixture(scope="module", params=list(MIXES))
def model(request):
    mod = MIXES[request.param]
    sizes = mod.tiny_sizes()
    return (mod.DRIVER.program_config(sizes, 256),
            mod.REFERENCE.make_weights(sizes, 5))


def _programs(cfg):
    """The chunk program and the tick as the engine jits them apart."""
    def window(params, tok, off, bufs, bt_row, slot, n):
        return decode_window_paged(
            params, tok, off, bufs, bt_row, cfg, page_size=PAGE, length=LEN,
            impl="kernel", n_valid=n, slot=slot, last_only=True)

    def tick(params, tok, pos, bufs, bt, active):
        counted = {}
        logits, bufs = decode_step_paged(
            params, tok, pos, bufs, bt, cfg, page_size=PAGE, length=LEN,
            active=active, impl="kernel", stats=counted)
        return logits, bufs, counted.get("moe")

    return jax.jit(window), jax.jit(tick)


def _prefilled(cfg, params, ids, lens):
    """A pool of ``len(lens)`` slots, slot ``b`` prefilled with ``lens[b]``
    tokens of ``ids[b]`` in chunks of ``CHUNK``."""
    B = len(lens)
    per = LEN // PAGE
    pool = hybrid.init_hybrid_pool(cfg, 1 + B * per, PAGE, B, LEN)
    bt = jnp.asarray(1 + np.arange(B * per).reshape(B, per), jnp.int32)
    window, tick = _programs(cfg)
    for b, n in enumerate(lens):
        for off in range(0, n, CHUNK):
            w = min(CHUNK, n - off)
            tok = np.zeros((1, CHUNK), np.int32)
            tok[0, :w] = ids[b, off:off + w]
            _, pool = window(params, jnp.asarray(tok),
                             jnp.asarray([off], jnp.int32), pool, bt[b:b + 1],
                             jnp.asarray(b, jnp.int32),
                             jnp.asarray([w], jnp.int32))
    return pool, bt, window, tick


@pytest.mark.parametrize("bucket,real", [(CHUNK, CHUNK), (16, 11)],
                         ids=["full_window", "short_bucket"])
def test_the_fused_walk_equals_the_chunk_program_then_the_tick(
        model, bucket, real):
    """Slots 0 and 1 decode at positions 40 and 67, slot 2 holds 32 tokens of
    its prompt and takes its next window of ``real`` tokens in a bucket of
    ``bucket``; slot 3 is empty."""
    cfg, params = model
    ids = np.random.default_rng(3).integers(
        1, cfg.vocab, (4, LEN)).astype(np.int32)
    lens = [40, 67, CHUNK, 0]
    pool, bt, window, tick = _prefilled(cfg, params, ids, lens)
    tok = jnp.asarray(ids[np.arange(4), lens])
    pos = jnp.asarray(lens, jnp.int32)
    active = jnp.asarray([True, True, False, False])
    chunk_ids = np.zeros((1, bucket), np.int32)
    chunk_ids[0, :real] = ids[2, CHUNK:CHUNK + real]
    chunk = (jnp.asarray(chunk_ids), jnp.asarray([CHUNK], jnp.int32),
             bt[2:3], jnp.asarray(2, jnp.int32),
             jnp.asarray([real], jnp.int32))

    want_last, after = window(params, chunk[0], chunk[1], pool, *chunk[2:])
    want_logits, want_pool, want_moe = tick(params, tok, pos, after, bt,
                                            active)

    def fused(params, tok, pos, pool, bt, active, chunk):
        counted = {}
        out = hybrid.tick_with_window(
            params, tok, pos, pool, bt, cfg, page_size=PAGE, chunk=chunk,
            active=active, stats=counted)
        return out + (counted.get("moe"),)

    logits, last, got_pool, moe = jax.jit(fused)(params, tok, pos, pool, bt,
                                                 active, chunk)
    live = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(logits)[live].argmax(-1),
                                  np.asarray(want_logits)[live].argmax(-1))
    assert np.abs(np.asarray(logits)[live]
                  - np.asarray(want_logits)[live]).max() < TOL
    assert np.abs(np.asarray(last) - np.asarray(want_last)).max() < TOL
    for i, (got, want) in enumerate(zip(got_pool, want_pool)):
        assert got.keys() == want.keys()
        for key in got:
            g, w = np.asarray(got[key]), np.asarray(want[key])
            if key == "kv":
                g, w = g[1:], w[1:]     # page 0 is the trash page
            if i == 0 and key == "conv":
                np.testing.assert_array_equal(g, w)
            assert np.abs(g - w).max() < TOL, (i, key)
    if "moe" in (cfg.ffn or ()):
        # the step routed the tick's rows and the window's real lanes: the
        # tick's own counts plus a pair a token an expert, none dropped
        count = dict(zip(MOE_STATS, np.asarray(moe)))
        routed = sum(f == "moe" for f in cfg.ffn) * cfg.routed.per_token
        assert count["pairs_routed"] == (2 + real) * routed
        assert count["pairs_dropped"] == count["pairs_misplaced"] == 0
        assert count["pairs_held"] >= np.asarray(want_moe)[1]


@pytest.mark.parametrize("real,assigned", [(11, 80), (5, 40)],
                         ids=["past_the_window", "under_the_padding_lanes"])
def test_what_the_trash_page_and_an_idle_row_hold_reach_no_token(
        model, real, assigned):
    """Page 0 takes whatever the fused decode kernel's idle output block held
    (on the chip: the bits of a float32 scratch read as bfloat16, NaN among
    them), a block table's unassigned entries point at it (past the window's
    positions, or under its padding lanes where the request ends inside the
    bucket), and a released slot's row still folds it at its stale position.
    Neither may reach a token: a key no lane may read has the value 0 in the
    fold, a key under a padding lane is the row that lane has just written to
    page 0 at its own offset, and a row that is no token is 0 before the
    routed feed-forward's placement products sum over rows. The walk with NaN
    there equals the walk with zeros, bit for bit."""
    cfg, params = model
    ids = np.random.default_rng(4).integers(
        1, cfg.vocab, (4, LEN)).astype(np.int32)
    lens = [40, 67, CHUNK, 0]
    pool, bt, _, _ = _prefilled(cfg, params, ids, lens)
    # slot 3 was released: no pages, an old position, idle
    bt = bt.at[3].set(0)
    # slot 2's request needs ``assigned`` positions: the rest of its row is
    # unassigned
    bt = bt.at[2, assigned // PAGE:].set(0)
    tok = jnp.asarray(ids[np.arange(4), lens])
    pos = jnp.asarray([40, 67, 0, 37], jnp.int32)
    active = jnp.asarray([True, True, False, False])
    chunk = (jnp.asarray(ids[2:3, CHUNK:CHUNK + 16]),
             jnp.asarray([CHUNK], jnp.int32), bt[2:3],
             jnp.asarray(2, jnp.int32), jnp.asarray([real], jnp.int32))

    fused = jax.jit(lambda pool: hybrid.tick_with_window(
        params, tok, pos, pool, bt, cfg, page_size=PAGE, chunk=chunk,
        active=active))
    dirty = [{k: v.at[0].set(jnp.nan) if k == "kv" else v
              for k, v in layer.items()} for layer in pool]
    want, got = fused(pool), fused(dirty)
    live = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got[0])[live],
                                  np.asarray(want[0])[live])
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for g, w in zip(got[2], want[2]):
        for key in g:
            a, b = np.asarray(g[key]), np.asarray(w[key])
            if key == "kv":
                a, b = a[1:], b[1:]
            else:
                a, b = a[:3], b[:3]     # the released slot's rows are nobody's
            np.testing.assert_array_equal(a, b)


def test_a_row_that_is_no_token_cannot_reach_the_routed_rows():
    """``moe_topk_held`` places rows by products with 0/1 matrices that sum
    over EVERY row: an invalid row's NaN would be every token's."""
    from mmlspark_tpu.parallel.moe import moe_topk_held
    cfg = test_lfm2_decoder.DRIVER.program_config(
        test_lfm2_decoder.tiny_sizes(), 64)
    params = test_lfm2_decoder.REFERENCE.make_weights(
        test_lfm2_decoder.tiny_sizes(), 1)
    lp = next(lp for lp in params["layers"] if "moe" in lp)
    x = jax.random.normal(jax.random.PRNGKey(0), (12, cfg.d_model))
    valid = jnp.arange(12) % 3 != 1
    want, counts = moe_topk_held(x, x, lp["moe"], cfg.routed, valid)
    bad = jnp.where(valid[:, None], x, jnp.nan)
    got, again = moe_topk_held(bad, bad, lp["moe"], cfg.routed, valid)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(valid)],
                                  np.asarray(want)[np.asarray(valid)])
    np.testing.assert_array_equal(np.asarray(again), np.asarray(counts))


# ---- the scheduler -----------------------------------------------------------

def _ticks_run(decoder):
    """Count the steps whose dispatch decoded rows, by wrapping the one place
    that dispatches."""
    ran = []
    inner = decoder._dispatch_tick

    def counted(decode_live, window=()):
        ran.append(bool(decode_live))
        return inner(decode_live, window)

    decoder._dispatch_tick = counted
    return ran


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(1, 97, n).astype(np.int32) for n in (20, 100, 70)]


def test_a_chunk_with_decodes_live_rides_and_a_solo_chunk_does_not(
        model, prompts):
    """One request alone: its chunk runs in the tick program with every row
    inactive and is no tick, and its tokens are the offline generator's. Two
    more behind it: their windows ride its ticks, the pool's tick count is
    the ticks run, and every request gets the same tokens (the row of a final
    chunk joins the NEXT tick)."""
    cfg, params = model
    alone = []
    for p in prompts:
        dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=LEN,
                                page_size=PAGE, prefill_chunk=CHUNK)
        ran = _ticks_run(dec)
        alone.append(drain(dec, [dec.submit(p, 12)])[0])
        np.testing.assert_array_equal(
            alone[-1], np.asarray(generate_cached(
                params, jnp.asarray(p)[None], cfg, 12))[0, p.size:])
        stats = dec._kv.stats
        assert stats["prefill_chunks"] == -(-p.size // CHUNK)
        assert stats["prefill_chunks_riding"] == 0
        # (the pipeline runs a tick or two past the last token)
        assert (stats["attn_ticks_kernel"] - stats["prefill_chunks"]
                == sum(ran) >= 11)

    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=LEN,
                            page_size=PAGE, prefill_chunk=CHUNK)
    ran = _ticks_run(dec)
    first = dec.submit(prompts[0], 12)
    while not first.tokens:
        dec.step()
    assert dec._kv.stats["prefill_chunks_riding"] == 0
    rest = [dec.submit(p, 12) for p in prompts[1:]]
    got = drain(dec, [first] + rest)
    for g, want in zip(got, alone):
        np.testing.assert_array_equal(g, want)
    stats = dec._kv.stats
    assert stats["prefill_chunks"] == 1 + 4 + 3
    # the first request's 11 ticks carried a window each until the others'
    # seven were through
    assert stats["prefill_chunks_riding"] == 7
    assert (stats["attn_ticks_kernel"] - stats["prefill_chunks"]
            == sum(ran)), "a fused step is one tick and one chunk"
    for label in ("gqa_window", "kda_window", "latent_window"):
        assert stats.get("attn_ticks_" + label, 0) == 0
    if "moe" in (cfg.ffn or ()):
        assert stats["moe_pairs_dropped"] == stats["moe_pairs_misplaced"] == 0
        assert stats["moe_pairs_routed"] > 0


def test_a_riding_chunk_is_under_the_tick_span(model, prompts):
    from mmlspark_tpu.observability import tracing as tr
    cfg, params = model
    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=LEN,
                            page_size=PAGE, prefill_chunk=CHUNK)
    first = dec.submit(prompts[0], 8)
    while not first.tokens:
        dec.step()
    mark = len(tr.span_log())
    second = dec.submit(prompts[2], 2)
    drain(dec, [first, second])
    spans = [s for s in tr.span_log()[mark:]
             if s[0] in ("decoder.tick", "continuous.prefill_chunk")]
    chunks = [s for s in spans if s[0] == "continuous.prefill_chunk"]
    ticks = [s for s in spans if s[0] == "decoder.tick"]
    assert len(chunks) == 3
    for _, _, t0, t1 in chunks:
        assert any(a <= t0 and t1 <= b for _, _, a, b in ticks)


def test_a_tick_of_several_steps_never_carries_a_chunk(model, prompts):
    cfg, params = model
    dec = ContinuousDecoder(params, cfg, max_slots=3, max_len=LEN,
                            page_size=PAGE, prefill_chunk=CHUNK,
                            steps_per_dispatch=2)
    assert not dec._carries
    reqs = [dec.submit(prompts[0], 12)]
    while not reqs[0].tokens:
        dec.step()
    reqs.append(dec.submit(prompts[1], 4))
    drain(dec, reqs)
    assert dec._kv.stats["prefill_chunks"] == 5
    assert dec._kv.stats["prefill_chunks_riding"] == 0


def test_a_dense_engine_never_rides():
    cfg = TransformerConfig(vocab=61, layers=2, d_model=32, heads=4, d_ff=64,
                            max_len=LEN, causal=True, dtype=jnp.float32)
    params = init_transformer(cfg, 0)
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=LEN,
                            page_size=PAGE, prefill_chunk=CHUNK)
    assert not dec._carries
    rng = np.random.default_rng(2)
    reqs = [dec.submit(rng.integers(1, 61, 12).astype(np.int32), 10)]
    while not reqs[0].tokens:
        dec.step()
    long = rng.integers(1, 61, 90).astype(np.int32)
    reqs.append(dec.submit(long, 4))
    got = drain(dec, reqs)
    assert dec._kv.stats["prefill_chunks"] == 3
    assert dec._kv.stats["prefill_chunks_riding"] == 0
    np.testing.assert_array_equal(
        got[1], np.asarray(generate_cached(params, long[None], cfg, 4))[
            0, long.size:])


@pytest.mark.parametrize("chunk,floor", [(CHUNK, CHUNK), (128, 64)])
def test_a_decoder_that_carries_pads_a_window_to_64_lanes_at_least(
        model, chunk, floor):
    """A program that carries a window costs the host a second or two to
    trace, lower and load whatever its width, so such a decoder keeps fewer
    of them: no window narrower than 64 lanes (or than the chunk, where that
    is less). A dense decoder's windows start at 8 as before."""
    cfg, params = model
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=LEN,
                            page_size=PAGE, prefill_chunk=chunk)
    assert dec._carries and dec._window_floor == floor
    widths = set()
    inner = dec._dispatch_tick

    def seen(decode_live, window=()):
        if window:
            widths.add(window[0].shape[1])
        return inner(decode_live, window)

    dec._dispatch_tick = seen
    rng = np.random.default_rng(3)
    reqs = [dec.submit(rng.integers(1, 97, n).astype(np.int32), 1)
            for n in (5, 70)]
    drain(dec, reqs)
    assert widths == ({CHUNK} if chunk == CHUNK else {64, 128})


def test_the_tick_that_carries_a_chunk_is_a_tick_to_the_trace(model):
    """Both programs are jitted from a function named ``tick``: the device
    trace files their operations under ``jit_tick``, where the benchmark's
    readers look for a tick's kernels and seconds (a second name would split
    a roofline's seconds from its bytes). They are two programs, and the
    plain one is what an engine with no window pending dispatches."""
    cfg, _ = model
    key = (cfg, PAGE, LEN, 1, None, False, False, "kernel", None, None, None,
           None)
    plain = continuous._tick_program(*key)
    carrying = continuous._tick_program(*key, chunk=True)
    assert plain is not carrying
    assert plain.__wrapped__.__name__ == carrying.__wrapped__.__name__ == "tick"
