"""The page an engine derives when it is given none, and the fused decode
kernel at the pages the rule gives GPT-2 XL.

1. THE RULE — ``derived_page_size``: sixteen pages a slot at ``max_len``,
   held to ``[16, 256]``, then the stored dtype's sublane tile where the
   kernel compiles for the chip; a model with sparse layers keeps its sparse
   block; an explicit ``page_size=`` wins; ``GenerationEngine`` forwards the
   same ``None``. The pool's ``stats`` and the two gauges say what was served.
2. THE KERNEL — ``_pa_fused_call`` against the gather oracle at 25 heads of
   64 in pages of 64 and 128 (what ``max_len`` 1024 and 2048 derive): windows
   of 1, 5 and 256 whose first row sits on a page's first row, on its last
   row, on no cached key at all, and whose writes straddle pages; the pages'
   bytes equal ``_paged_writeback``'s bit for bit.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.models.zoo.transformer import (
    TransformerConfig, decode_window_paged, init_paged_cache,
    init_transformer)
from mmlspark_tpu.serving import continuous, kv_pool
from mmlspark_tpu.serving.continuous import (ContinuousDecoder,
                                             derived_page_size)
from mmlspark_tpu.serving.generation import GenerationEngine

TINY = TransformerConfig(vocab=64, layers=1, d_model=16, heads=2, d_ff=32,
                         max_len=64, causal=True, norm="rmsnorm",
                         position="rope", dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def tiny_params():
    return init_transformer(TINY, seed=0)


# max_len -> the page derived; the chip stores bf16 in tiles of 16 rows and
# int8 in tiles of 32
RULE = [(64, 16), (256, 16), (1000, 64), (1024, 64), (4096, 256),
        (65536, 256)]
TILE = {None: 16, "int8": 32}


@pytest.mark.parametrize("kv_dtype", list(TILE), ids=["bf16", "int8"])
@pytest.mark.parametrize("max_len,page", RULE,
                         ids=[f"max_len{r[0]}" for r in RULE])
@pytest.mark.parametrize("backend", ["interpret", "chip"])
def test_decoder_derives_its_page_from_max_len(
        tiny_params, monkeypatch, backend, max_len, page, kv_dtype):
    """On the CPU the derived page is served as it is; where the kernel
    compiles for the chip (steered here, nothing runs) it is rounded up to
    the stored dtype's tile, as an explicit page always was."""
    assert derived_page_size(TINY, max_len) == page
    if backend == "chip":
        monkeypatch.setattr(continuous, "_pa_auto_interpret", lambda: False)
        page = -(-page // TILE[kv_dtype]) * TILE[kv_dtype]
    dec = ContinuousDecoder(tiny_params, TINY, max_slots=2, max_len=max_len,
                            kv_dtype=kv_dtype)
    per_slot = -(-max_len // page)
    assert dec._page == dec._kv.page_size == page
    assert dec._bt_host.shape == (2, per_slot)
    assert dec._kv.buffers[0]["kv"].shape == (
        1 + 2 * per_slot + per_slot, TINY.heads, page,
        2 * (TINY.d_model // TINY.heads))
    assert dec._kv.stats["page_size"] == page
    assert dec._kv.stats["pages_per_slot"] == per_slot
    assert kv_pool.M_PAGE_SIZE.labels().get() == page
    assert kv_pool.M_PAGES_PER_SLOT.labels().get() == per_slot


@pytest.mark.parametrize("page_size", [None, 8, 32, 128])
def test_engine_and_decoder_agree_and_an_explicit_page_wins(tiny_params,
                                                            page_size):
    kw = {} if page_size is None else {"page_size": page_size}
    dec = ContinuousDecoder(tiny_params, TINY, max_slots=2, max_len=1024,
                            **kw)
    eng = GenerationEngine(tiny_params, TINY, max_slots=2, max_len=1024,
                           **kw)
    try:
        assert dec._page == eng.decoder._page == (page_size or 64)
        assert eng.decoder._kv.stats["pages_per_slot"] == dec._P_max
    finally:
        eng.stop()


def test_an_explicit_page_is_still_validated(tiny_params):
    with pytest.raises(ValueError, match="page_size must be >= 1"):
        ContinuousDecoder(tiny_params, TINY, max_len=1024, page_size=0)


# ---------------------------------------------------------------------------
# the fused kernel at GPT-2 XL's head geometry, in the derived pages

HEADS, HD, SLOT_LEN = 25, 64, 512


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def xl(request):
    """One decoder layer 25 heads of 64 wide (GPT-2 XL's attention), the
    rest of the block small."""
    cfg = TransformerConfig(vocab=32, layers=1, d_model=HEADS * HD,
                            heads=HEADS, d_ff=32, max_len=SLOT_LEN,
                            causal=True, norm="layernorm",
                            position="learned",
                            dtype=jnp.dtype(request.param))
    return cfg, init_transformer(cfg, seed=3)


def first_rows(page, W):
    """Four rows' first window positions: the last rows of a page (a window
    of one sits ON the last row, a longer one straddles into the next, 256
    over several), a page's first row, no cached key at all, and the window
    that ends on the slot's last row; the fifth row is inactive."""
    return [page - min(W, 3), page, 0, SLOT_LEN - W, 7]


@pytest.mark.parametrize("W", [1, 5, 256])
@pytest.mark.parametrize("page", [64, 128])
def test_fused_kernel_equals_the_gather_oracle_at_xl_heads(xl, page, W):
    cfg, params = xl
    B, per_slot = 5, SLOT_LEN // page
    rng = np.random.default_rng(page + W)
    # every slot's pages scattered over the pool, none in table order
    bt = 1 + rng.permutation(B * per_slot).reshape(B, per_slot)
    pages = init_paged_cache(cfg, 1 + B * per_slot, page)
    pages = [{"kv": jnp.asarray(
        rng.normal(0, 1, c["kv"].shape), c["kv"].dtype)} for c in pages]
    bt = jnp.asarray(bt, jnp.int32)
    pos = jnp.asarray(first_rows(page, W), jnp.int32)
    active = jnp.asarray([True] * 4 + [False])
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, W)))
    want, want_pages = decode_window_paged(
        params, toks, pos, pages, bt, cfg, page_size=page, length=SLOT_LEN,
        active=active, impl="gather")
    got, got_pages = decode_window_paged(
        params, toks, pos, pages, bt, cfg, page_size=page, length=SLOT_LEN,
        active=active, impl="kernel")
    got, want = np.asarray(got)[:4], np.asarray(want)[:4]
    assert np.isfinite(got).all()
    tol = 2e-5 if cfg.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    if cfg.dtype == jnp.float32:
        assert np.array_equal(got.argmax(-1), want.argmax(-1))
    # the window's rows land in their pages as _paged_writeback puts them,
    # bit for bit, across a page boundary too; nothing else off the trash
    # page changes, and the inactive row's pages are as they were
    new, ref, old = (np.asarray(p[0]["kv"]) for p in (
        got_pages, want_pages, pages))
    assert np.array_equal(new[1:], ref[1:])
    changed = {int(p) for p in np.flatnonzero(
        (new != old).any(axis=(1, 2, 3))) if p}
    written = {int(bt[b, t // page]) for b in range(4)
               for t in range(int(pos[b]), int(pos[b]) + W)}
    assert changed == written

