"""Paged attention — Pallas TPU decode kernel over the KV page pool.

PR 7's paged decode path is *gather-then-attend*: every tick copies each
row's pages into a contiguous ``(B, H, L, hd)`` scratch
(``models/zoo/transformer.py:paged_gather``), runs the ragged step, and
scatters the one fresh K/V position back (``_paged_writeback``). That
gather is an O(B·L)×layers HBM round-trip per decode tick that grows
linearly with context — pure data movement, zero FLOPs of value. This
module removes it: a vLLM-style PagedAttention kernel that walks the
BLOCK TABLE and reads K/V pages **in place**, carrying FlashAttention's
online-softmax accumulators in VMEM, so per-tick HBM traffic is one read
of the live pages plus one page-granular write — never a contiguous
materialization.

Design notes (TPU-first):

* THE POOL'S LAYOUT: one buffer a layer, ``(num_pages, H, page, 2*hd)``,
  K in lanes ``[0, hd)`` and V in ``[hd, 2*hd)`` of the minor axis
  (:func:`pack_kv` / :func:`split_kv`). Not two ``(N, H, page, hd)``
  buffers: at ``hd = 64`` their minor axis is half a 128-lane vector
  register, the TPU keeps such an array with the PAGE INDEX minor-most
  (``{0,3,2,1}``: row-major would pad every row to twice its bytes) and
  the Mosaic call takes row-major operands only, so XLA relaid the whole
  pool in and out of every call that aliased it: four pool-sized copies
  a layer in the tick, in a prefill chunk and in an insertion, 69% of the
  generation cell's device time (PERF.md, PR 28). Packed, the minor axis
  is a whole register at every ``hd`` that is a multiple of 64, the pool
  stays row-major on the device, and the aliased call and the donated
  programs update it in place. The ``(N, H, page)`` scale pools of a
  quantized pool keep their shape (1/64 of the bytes).
* THE GRID IS THE BATCH'S LIVE PAGES: one ragged sweep of
  ``sum over rows of (pages that row needs)`` steps, not ``slots x pages a
  slot`` (a step that fetches a page and folds nothing costs ~0.5 us, one
  that moves nothing ~0.1 us: PERF.md, PR 34). :func:`_schedule` builds, on
  the device and once a tick (the layers' calls share it), the int32
  vectors that say which ``(row, page)`` step ``s`` is, rows in order and a
  row's pages in order; the number of steps is a TRACED grid bound, so one
  compiled program serves every batch of contexts. A row needs the pages
  that hold its keys and, in a fused call, those its window writes; a row
  with nothing to read or write takes one step, which writes out its
  (meaningless) context. A page block is ``(1, H, page, 2*hd)``, K and V of
  every head in one DMA. A step of the LATENT sweep is a BLOCK of up to
  ``k`` consecutive pages of one row (PR 44; ``k`` page operands on the one
  pool, :func:`latent_block`): a whole block is one fold, a row's last
  block folds page by page, and a page the row does not need is neither
  fetched nor folded. A row of 94 pages is 24 steps at ``k`` = 4.
* WHAT A LIVE STEP COMPUTES ON ITS BLOCK (PR 37). The scores take the keys
  AS STORED: a bf16 query meets bf16 keys, and the products of two bf16
  values are exact in the float32 they are summed in, so the scores equal
  a float32 copy's up to the order of the sum (a float32 query or
  dequantized keys lift the other side). The weights ``p`` stay float32 and
  the values are lifted to them: on the chip the MXU rounds both operands
  to bf16 at its default precision whatever their type (the kernel alone
  returns the same bits with ``p`` rounded first), and wherever it does not
  (interpret mode: every test) a rounded ``p`` is another result. A window
  of several queries folds a product a head: the block split on the lane
  axis in VMEM, a state row a window row.
* A ONE-QUERY WINDOW KEEPS ITS STATE A ROW A HEAD. The query block is
  ``(1, H, Wp, hd)`` with ``Wp`` a register's sublanes (16 for bf16), so a
  decode tick's one query a head would drag fifteen padding rows through
  every update of ``m``, ``l`` and the accumulator: 150 registers of state
  for GPT-2 XL's 25 heads, and THAT was a live step's cost, not its
  products (PERF.md, PR 37). With ``W == 1`` (static; whatever the dtypes)
  the state is ``(G, 8, .)``: the heads on the sublane axis, eight a group
  (``_HEADS``: one float32 register each of ``m``, ``l``, accumulator). A
  group is then ONE head whose window is its eight queries and whose keys
  are all eight heads' ``8 * page`` packed rows laid head after head (a
  free reshape of the block's leading axes) under a block-diagonal mask:
  query ``h`` sees its own head's keys below the row's bound and weighs
  every other head's by an exact 0. The packed row is not split there: the
  query operand is zero over the V lanes (built once a row, at its first
  step, into a fourth scratch) and ``p . [K | V]`` lands in an accumulator
  ``2*hd`` wide whose V half is sliced ONCE a row, in ``_finalize``. All of
  that adds exact zeros as long as ``0 x anything read`` is 0: every lane a
  step reads is FINITE, because the pool is zero-initialised, only finite
  rows are ever written, and a page no row needs is never visited (tests:
  NaN in the unneeded pages, the largest finite bf16 past a row's bound in
  the pages it needs). The K half of the accumulator is finite garbage
  nobody reads. Groups are always WHOLE (:func:`_whole_groups`: where ``H``
  is no multiple of eight the last eight heads are folded once more as the
  last group), so every product has one shape whatever ``H`` is and a
  head's context is the same bits whichever heads share the call: a mesh's
  head shards return what one device does (tests).
* the physical page for grid step ``s`` comes from the scalar-prefetched
  block table: the BlockSpec index_map reads ``bt[row_of[s], page_of[s]]``
  (``PrefetchScalarGridSpec``), which is exactly the indirection
  ``paged_gather`` used to materialize. Every VMEM byte a step reads was
  DMA'd for that step by the pipeline. Pages a row does not need are never
  visited, whatever its block table holds there; the keys of its last page
  past the row's length are masked by the per-row bound.
* running ``m``/``l`` live in VMEM scratch shaped ``(H, W, LANE)``
  (lane-replicated, as in ``flash_attention.py``); the f32 context
  accumulator is ``(H, W, hd)`` (a one-query window: ``(G, 8, LANE)`` and
  ``(G, 8, 2*hd)``). Masked logits use ``-1e30`` — a fully
  masked row yields ``l == 0`` and the final divide guards it to zeros
  rather than NaN.
* the FUSED variant (:func:`paged_attention_window`) also scatters the
  window's fresh K/V rows into their pages in the same launch, replacing
  the separate per-tick writeback. The window rows ride along as one
  direct ``(B, H, W, 2*hd)`` input, packed like a page, folded into the
  online softmax under an in-window causal mask, so pages only ever supply keys strictly before
  ``pos[b]`` — reading each page's *pre-scatter* content is therefore
  exact. The scatter itself goes through ``input_output_aliases``: the
  page-pool output aliases the input and its index_map redirects every
  page outside the row's write range to trash page 0, so Pallas's
  write-on-index-change semantics make the real page writes O(1) per row
  instead of O(context).
* page-write exclusivity is a CALLER contract: a page inside any row's
  write range (``pos[b] .. pos[b]+W-1``) must be exclusively owned by
  that row. The pool's copy-on-write admission guarantees this — shared
  prefix pages are never written (serving/kv_pool.py).
* MESH MOUNT: a bare ``pallas_call`` inside a sharded jit is not
  GSPMD-partitionable — XLA would gather the whole pool onto one
  device. ``paged_attention``/``paged_attention_window`` therefore take
  ``mesh=`` and mount the kernel via ``jax.shard_map`` with heads split
  over the ``tp`` axis: Q, the page pools and the online-softmax VMEM
  scratch all shard on the head axis (specs
  ``P(slot_axis, head_axis, None, None)`` / ``P(None, head_axis, None,
  None)``), each shard runs the UNCHANGED kernel over its ``heads/tp``
  slice, and only the caller's post-attention projection pays an ICI
  collective (GSPMD inserts it, exactly as for ``transformer_apply``).
  Slots optionally shard over ``dp``. Under a mesh the mount is
  READ-ONLY — the fused in-kernel scatter cannot run per-shard when
  slots split over ``dp`` while the pool replicates over it (each dp
  shard would apply only its own rows' writes and the replicas would
  diverge) — so the window's fresh K/V rows are written OUTSIDE the
  mount by :func:`_pool_write_rows`, a GSPMD-partitionable scatter that
  writes bytes bit-identical to both ``_paged_writeback`` and the fused
  kernel's in-launch scatter.

Tiling contract: the page dimension sits in the SUBLANE slot of the
``(1, H, page, 2*hd)`` block, so on a real TPU ``page_size`` must be a
multiple of the dtype's sublane tile — 8 (f32), 16 (bf16), 32 (int8);
see :func:`sublane_multiple` / :func:`aligned_page_size` and
``PagedKVPool.kernel_aligned_page_size``. Interpret mode (the CI path on
``JAX_PLATFORMS=cpu``, chosen automatically like ``flash_attention``'s
``_auto_interpret``) has no such constraint.

``MMLSPARK_TPU_PAGED_ATTN=gather`` selects PR 7's gather path as a
fallback; :func:`resolve_impl` is the one resolver every layer shares.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from .pallas_kernels import _LANE, _round_up
from .kv_quant import quantize_kv

__all__ = ["paged_attention", "paged_attention_window",
           "paged_attention_gqa",
           "paged_attention_selected", "paged_attention_latent",
           "resolve_impl",
           "sublane_multiple", "aligned_page_size", "pack_kv", "split_kv",
           "stored_kv"]

_NEG = -1e30

#: heads a group where a call's window is ONE query: a float32 register's
#: sublanes, so a group's running max, denominator and accumulator are one
#: register each (:func:`_window_state`)
_HEADS = 8

#: env knob — process default for the paged-attention implementation.
ENV_KNOB = "MMLSPARK_TPU_PAGED_ATTN"

_IMPLS = {"kernel": "kernel", "fused": "kernel", "auto": "kernel",
          "default": "kernel", "": "kernel",
          "gather": "gather", "xla": "gather", "reference": "gather"}


def resolve_impl(override: Optional[str] = None) -> str:
    """Resolve the paged-attention implementation: an explicit
    ``override`` wins, else the ``MMLSPARK_TPU_PAGED_ATTN`` env knob,
    else ``"kernel"``. Returns ``"kernel"`` or ``"gather"``.

    Resolved EAGERLY by callers (the engine resolves once at
    construction and threads the choice into its compiled-program cache
    keys) — resolving inside a trace would bake one process-wide env
    read into every cached program."""
    raw = override if override is not None else os.environ.get(ENV_KNOB, "")
    key = str(raw).strip().lower()
    if key not in _IMPLS:
        raise ValueError(
            f"unknown paged-attention impl {raw!r} "
            f"(choose 'kernel' or 'gather')")
    return _IMPLS[key]


def sublane_multiple(dtype) -> int:
    """The TPU sublane tile for ``dtype`` — the unit ``page_size`` must
    divide into for the kernel's ``(1, H, page, 2*hd)`` page blocks."""
    itemsize = jnp.dtype(dtype).itemsize
    return max(8, 32 // max(1, itemsize))


def aligned_page_size(page_size: int, dtype) -> int:
    """Round ``page_size`` up to the kernel-tileable multiple for
    ``dtype`` (identity whenever it already complies)."""
    return _round_up(max(1, int(page_size)), sublane_multiple(dtype))


def _auto_interpret() -> bool:
    from ..utils.device import is_tpu
    return not is_tpu()


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def pack_kv(k, v):
    """K and V ``(..., hd)`` side by side on the minor axis,
    ``(..., 2*hd)``: the layout of a page-pool buffer (module docstring)."""
    return jnp.concatenate([k, v], axis=-1)


def split_kv(kv):
    """The ``(k, v)`` halves of a packed ``(..., 2*hd)`` array."""
    hd = kv.shape[-1] // 2
    return kv[..., :hd], kv[..., hd:]


def _scores(q, k, scale):
    """``q . k^T * scale`` (H, W, K) in float32. The operands meet in the
    wider of their two dtypes: a bf16 query takes bf16 keys AS STORED (the
    products of two bf16 values are exact in the float32 they are summed
    in), a float32 query or dequantized keys lift the other side."""
    dt = jnp.promote_types(q.dtype, k.dtype)
    return jax.lax.dot_general(
        q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale


def _weigh(p, v):
    """``p . v`` (H, W, value width): the values are lifted to the float32
    weights (PERF.md, PR 37: rounding ``p`` instead is worth nothing on
    the chip, where the MXU rounds both anyway and the bits are the same,
    and is a precision change wherever nothing rounds: interpret mode)."""
    return jax.lax.dot_general(
        p, v.astype(p.dtype), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _fold(m_scr, l_scr, acc_scr, s, valid, weigh):
    """One online-softmax update: fold the score block ``s`` (H, W, K)
    with key-validity ``valid`` (broadcastable) into the running
    (m, l, acc) VMEM state; ``weigh(p)`` is the weights' product with the
    keys' values (:func:`_weigh`)."""
    s = jnp.where(valid, s, _NEG)
    m_prev = m_scr[..., 0:1]                           # (H, W, 1)
    l_prev = l_scr[..., 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # `valid` (not the _NEG sentinel) zeroes masked probabilities: for a
    # row with every key masked so far, m_new == _NEG and exp(s - m_new)
    # would be exp(0) == 1 on the masked entries.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)                      # <= 1
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + weigh(p)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _fold_keys(m_scr, l_scr, acc_scr, q, kv, valid, scale, v_width=None):
    """Fold the keys of packed rows ``kv`` (H, K, .) that ``valid`` allows
    under the queries ``q`` (H, W, hd), a product a head. The rows split on
    the lane axis into key and value; with ``v_width`` they are LATENT: the
    whole row is the key and its first ``v_width`` values the value."""
    k, v = split_kv(kv) if v_width is None else (kv, kv[..., :v_width])
    _fold(m_scr, l_scr, acc_scr, _scores(q, k, scale), valid,
          functools.partial(_weigh, v=v))


def _whole_groups(x, size=_HEADS):
    """Heads ``(H, ...)`` as WHOLE groups of ``size`` (``_HEADS`` query
    heads; the KV heads they share where a KV head serves several), a list
    of ``(groups, size, ...)`` arrays cut from ``x`` without a copy: the
    heads in order and, where ``H`` is no multiple, the LAST ``size``
    heads once more as the last group (fewer heads than a group are padded
    with zero heads). Every group's products then have one shape whatever
    ``H`` is, so a head's bits do not depend on the heads beside it (module
    docstring). :func:`_heads_of` is the way back."""
    H, part = x.shape[0], x.shape[0] % size
    if H < size:
        x, H, part = jnp.pad(x, ((0, size - H),) + ((0, 0),) *
                             (x.ndim - 1)), size, 0
    return [t.reshape(-1, size, *x.shape[1:])
            for t in (x[:H - part], x[H - size:] if part else x[:0])
            if t.shape[0]]


def _heads_of(rows, H):
    """The ``H`` heads' rows ``(H, ...)`` of a heads-as-rows state
    ``(G, _HEADS, ...)`` grouped by :func:`_whole_groups`."""
    rows = rows.reshape(-1, *rows.shape[2:])
    part = H % _HEADS
    if H < _HEADS or not part:
        return rows[:H]
    return jnp.concatenate([rows[:H - part], rows[rows.shape[0] - part:]])


def _heads_query(q_ref, width):
    """The query operand of a ONE-query window, heads as rows: window row 0
    of every head of the ``(1, H, Wp, hd)`` block, ``_HEADS`` heads a group
    on the sublane axis (:func:`_whole_groups`) and zero-padded to the
    packed row's ``width`` (its lanes past ``hd`` hold V, and meet zeros):
    ``(G, _HEADS, width)``."""
    hd = q_ref.shape[3]
    # grouped in float32, whose register holds _HEADS rows: a free reshape
    q = jnp.concatenate(_whole_groups(q_ref[0, :, 0, :].astype(jnp.float32)))
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - hd))).astype(q_ref.dtype)


def _fold_heads(m_scr, l_scr, acc_scr, q, kv, n, scale, share=1):
    """Fold the first ``n`` keys of every head of packed rows ``kv``
    (Hkv, K, 2*hd) into a heads-as-rows state ``(G, _HEADS, .)`` under the
    query operand ``q`` (:func:`_heads_query`): a group's rows laid head
    after head are one head's ``_HEADS * K`` keys under a block-diagonal
    mask, the packed row unsplit (module docstring: exact zeros, as long as
    every lane read is finite). The groups in order are ONE batched product
    and the last, overlapping group one more (PERF.md, PR 37).

    Grouped queries: ``share`` query heads (a power of two) read KV head ``h
    // share``: a group's ``_HEADS`` state rows span ``_HEADS // share`` KV
    heads, ``share`` neighbouring rows own one block of the mask."""
    if share > _HEADS:      # whole groups a KV head: one product a KV head
        return _fold_wide(m_scr, l_scr, acc_scr, q, kv, n, scale, share)
    K, width = kv.shape[1:]
    span = _HEADS // share          # KV heads a group of state rows reads
    parts, g = [], 0
    for t in _whole_groups(kv, span):
        parts.append((slice(g, g + t.shape[0]),
                      t.reshape(t.shape[0], span * K, width)))
        g += t.shape[0]
    s = jnp.concatenate([_scores(q[gs], kvg, scale) for gs, kvg in parts])
    head = jax.lax.broadcasted_iota(jnp.int32, (1, _HEADS, 1), 1)
    if share > 1:
        head = head >> (share.bit_length() - 1)
    lo = K * head
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, span * K), 2)
    own = (col >= lo) & (col < lo + jnp.clip(n, 0, K))
    _fold(m_scr, l_scr, acc_scr, s, own, lambda p: jnp.concatenate(
        [_weigh(p[gs], kvg) for gs, kvg in parts]))


def _finalize(o_ref, l_scr, acc_scr, by_head=False):
    """The context out: the accumulator over the denominator. ``by_head``:
    the state's rows are the heads and its accumulator a packed row wide
    (:func:`_fold_heads`); its V half is the context, which every window
    row of a head takes (row 0 is the query's, the caller slices the
    padding rows off)."""
    l = l_scr[..., 0:1]
    ctx = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
    if by_head:
        H, _, hd = o_ref.shape[1:]
        ctx = jnp.broadcast_to(_heads_of(ctx[..., hd:], H)[:, None, :],
                               o_ref.shape[1:])
    o_ref[0] = ctx.astype(o_ref.dtype)


def _page_kv(kv_ref, ks_ref=None, vs_ref=None):
    """One page block's packed rows ``(H, page, .)`` AS STORED: the
    products take their operands from it (:func:`_scores`, :func:`_weigh`).
    With the page's two ``(1, H, page)`` scale blocks this is the
    IN-KERNEL dequant, to float32: they arrived through the same
    block-table index_map, so the multiply (the K lanes by ``ks``, the V
    lanes by ``vs``) happens in VMEM right after the page DMA and the
    quantized bytes are all HBM ever moves."""
    kv = kv_ref[0]
    if ks_ref is None:
        return kv
    hd = kv.shape[-1] // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * hd), 2)
    return kv.astype(jnp.float32) * jnp.where(
        lane < hd, ks_ref[0].astype(jnp.float32)[:, :, None],
        vs_ref[0].astype(jnp.float32)[:, :, None])


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _pages_fold(m_scr, l_scr, acc_scr, q, kv, p, bound, scale, page,
                v_width=None):
    """Fold page ``p``'s keys ``p*page ..`` strictly below ``bound``."""
    t = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    _fold_keys(m_scr, l_scr, acc_scr, q, kv, t < bound, scale, v_width)


def _window_attend(state, q_scr, q_ref, kvn_ref, page_kv, p, pos, scale,
                   page, W, share=1):
    """The attention of one grid step of a windowed kernel. A row's first
    step (p == 0) starts its ``state`` and folds the window's own rows: they
    arrive unquantized and packed like a page (``(1, H, Wp, 2*hd)``: direct
    inputs, not pages), folded under the in-window causal mask. A page with
    keys strictly below ``pos`` folds them; ``page_kv()`` reads its block.
    A window of ONE query (``W == 1``, static: ``q_scr`` holds its query
    operand, made once a row) folds heads as rows (:func:`_fold_heads`,
    ``share`` query heads a KV head); its causal mask is its first key."""
    from jax.experimental import pallas as pl

    by_head = W == 1
    assert by_head == bool(q_scr) and (by_head or share == 1)

    @pl.when(p == 0)
    def _init_and_window():
        _init(*state)
        if by_head:
            q_scr[0][...] = _heads_query(q_ref, q_scr[0].shape[-1])
            _fold_heads(*state, q_scr[0][...], kvn_ref[0], W, scale, share)
        else:
            Wp = q_ref.shape[2]
            row = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 1)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 2)
            # query j sees window keys j' <= j; padding key rows never
            # (padding QUERY rows keep every real key — they need a
            # nonzero denominator and their output is sliced off
            # host-side)
            valid = jnp.logical_and(
                jnp.logical_or(col <= row, row >= W), col < W)
            _fold_keys(*state, q_ref[0], kvn_ref[0], valid, scale)

    @pl.when(p * page < pos)
    def _pages():
        if by_head:
            _fold_heads(*state, q_scr[0][...], page_kv(), pos - p * page,
                        scale, share)
        else:
            _pages_fold(*state, q_ref[0], page_kv(), p, pos, scale, page)


def _overlay(blk, new_ref, pos, p, page, W, ridx):
    """``blk`` (one page of a pool buffer, page positions on axis 1) with
    the window rows that land in page ``p`` laid over it, in the pool's
    dtype: no float32 round-trip, so the written bytes are bit-identical
    to ``_paged_writeback``'s. ``ridx`` is the iota over axis 1."""
    for j in range(W):                                  # W static, small
        hit = ridx == pos + j - p * page                # all-False if out
        blk = jnp.where(hit, new_ref[0, :, j:j + 1], blk)
    return blk


# Four kernels, one contract. The grid is ONE ragged sweep (:func:`_schedule`):
# step ``s`` is page ``page_of[s]`` of row ``row_of[s]``, a row's pages in
# order and only the pages it needs, and the number of steps is a traced
# bound, so a call costs the batch's live pages whatever ``max_len`` is. A
# row's first step (p == 0) initialises the accumulators, its last
# (p == last_of[b]) writes the context out. The page block is
# (1, H, page, 2*hd), K in lanes [0, hd) and V in [hd, 2*hd); a quantized
# pool adds two (1, H, page) scale blocks riding the SAME block-table
# index_map (``quant``: they follow the page block among the operands). The
# *read* kernel attends ``len_ref[b]`` cached keys; the *window* kernel folds
# the window's own rows once at p == 0 and masks page keys STRICTLY below
# ``pos[b]``, so page blocks are always read pre-scatter; the two *fused*
# kernels (plain and quantized pages) also write the window's rows into
# their pages through the aliased pool outputs (every grid step outside the
# row's write range leaves its trash-directed output block untouched). The
# mesh mount runs the window kernel: under a mesh the fresh rows are written
# outside the mount (:func:`_pool_write_rows`).

def _step(row_ref, page_ref, last_ref):
    """``(row, page, is the row's last step)`` of this grid step."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    b, p = row_ref[s], page_ref[s]
    return b, p, p == last_ref[b]


def _pa_read_kernel(row_ref, page_ref, last_ref, bt_ref, len_ref, q_ref,
                    kv_ref, *rest, scale, page, quant):
    from jax.experimental import pallas as pl

    scales, (o_ref, m_scr, l_scr, acc_scr) = rest[:2 * quant], rest[2 * quant:]
    b, p, last = _step(row_ref, page_ref, last_ref)
    pl.when(p == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    bound = len_ref[b]

    @pl.when(p * page < bound)
    def _compute():
        _pages_fold(m_scr, l_scr, acc_scr, q_ref[0],
                    _page_kv(kv_ref, *scales), p, bound, scale,
                    page)

    pl.when(last)(lambda: _finalize(o_ref, l_scr, acc_scr))


def _pa_window_kernel(row_ref, page_ref, last_ref, bt_ref, pos_ref, q_ref,
                      kvn_ref, kv_ref, *rest, scale, page, W, quant):
    from jax.experimental import pallas as pl

    scales, (o_ref, m_scr, l_scr, acc_scr, *q_scr) = (rest[:2 * quant],
                                                       rest[2 * quant:])
    b, p, last = _step(row_ref, page_ref, last_ref)
    _window_attend((m_scr, l_scr, acc_scr), q_scr, q_ref, kvn_ref,
                   lambda: _page_kv(kv_ref, *scales), p, pos_ref[b], scale,
                   page, W)
    pl.when(last)(lambda: _finalize(o_ref, l_scr, acc_scr, W == 1))


def _pa_fused_kernel(row_ref, page_ref, last_ref, bt_ref, pos_ref, wlo_ref,
                     whi_ref, q_ref, kvn_ref, kv_ref, o_ref, kvo_ref,
                     m_scr, l_scr, acc_scr, *q_scr, scale, page, W, share=1):
    from jax.experimental import pallas as pl

    b, p, last = _step(row_ref, page_ref, last_ref)
    pos = pos_ref[b]
    _window_attend((m_scr, l_scr, acc_scr), q_scr, q_ref, kvn_ref,
                   lambda: _page_kv(kv_ref), p, pos, scale, page, W, share)

    @pl.when(jnp.logical_and(p >= wlo_ref[b], p <= whi_ref[b]))
    def _scatter():
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
        kvo_ref[0] = _overlay(kv_ref[0], kvn_ref, pos, p, page, W, ridx)

    pl.when(last)(lambda: _finalize(o_ref, l_scr, acc_scr, W == 1))


def _pa_fused_kernel_q(row_ref, page_ref, last_ref, bt_ref, pos_ref, wlo_ref,
                       whi_ref, q_ref, kvn_ref, kvq_ref, ksn_ref, vsn_ref,
                       kv_ref, ks_ref, vs_ref, o_ref, kvo_ref, kso_ref,
                       vso_ref, m_scr, l_scr, acc_scr, *q_scr, scale, page,
                       W):
    """The quantized fused kernel folds the window's UNQUANTIZED rows
    (``kvn``) and writes their quantized twins (``kvq`` with the per-head
    scales ``ksn``/``vsn``, all through :func:`quantize_kv` in the
    caller: the sanctioned helper, so every writer agrees bit for bit)."""
    from jax.experimental import pallas as pl

    b, p, last = _step(row_ref, page_ref, last_ref)
    pos = pos_ref[b]
    _window_attend((m_scr, l_scr, acc_scr), q_scr, q_ref, kvn_ref,
                   lambda: _page_kv(kv_ref, ks_ref, vs_ref), p, pos, scale,
                   page, W)

    @pl.when(jnp.logical_and(p >= wlo_ref[b], p <= whi_ref[b]))
    def _scatter():
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
        sidx = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        kvo_ref[0] = _overlay(kv_ref[0], kvq_ref, pos, p, page, W, ridx)
        kso_ref[0] = _overlay(ks_ref[0], ksn_ref, pos, p, page, W, sidx)
        vso_ref[0] = _overlay(vs_ref[0], vsn_ref, pos, p, page, W, sidx)

    pl.when(last)(lambda: _finalize(o_ref, l_scr, acc_scr, W == 1))


def _schedule(bound, whi, page, n_pages):
    """The ragged sweep of one call: ``(row_of, page_of, last_of, total)``.

    Row ``b`` needs ``n_b`` pages: those that hold a key below ``bound[b]``
    and, for a fused call, those up to the last page its window writes
    (``whi[b]``, below 0 for none: at ``pos % page == 0`` the write page
    lies after the last page with keys), at least one, so that every row
    initialises and writes out its context. Step ``s`` of the
    ``total = sum(n_b)`` grid steps is page ``page_of[s]`` of row
    ``row_of[s]``, rows in order and a row's pages in order, and
    ``last_of[b] = n_b - 1``. The two ``(B * n_pages,)`` int32 vectors are
    whole only up to ``total``: entries past it are never visited."""
    B = bound.shape[0]
    n = jnp.clip(jnp.maximum(-(-bound // page), whi + 1), 1,
                 n_pages).astype(jnp.int32)
    ends = jnp.cumsum(n)
    steps = jnp.arange(B * n_pages, dtype=jnp.int32)
    done = steps[:, None] >= ends[None, :]        # rows wholly before step s
    row_of = jnp.minimum(jnp.sum(done, axis=1), B - 1).astype(jnp.int32)
    page_of = jnp.minimum(
        steps - jnp.sum(jnp.where(done, n[None, :], 0), axis=1),
        n_pages - 1).astype(jnp.int32)
    return row_of, page_of, n - 1, ends[-1]


def _softmax_state(H, W, acc):
    """The VMEM scratch of an online softmax over ``(H, W)`` state rows:
    the running max ``m`` and denominator ``l`` (lane-replicated) and the
    float32 context accumulator, ``acc`` wide."""
    return [_vmem((H, W, _LANE), jnp.float32),
            _vmem((H, W, _LANE), jnp.float32),
            _vmem((H, W, acc), jnp.float32)]


def _window_state(q, W):
    """The VMEM scratch of a windowed call over queries ``q`` (B, H, Wp, hd)
    of which ``W`` are real: a state row a window row a head; a window of
    ONE query (module docstring) a row a HEAD, ``_HEADS`` heads a group, the
    accumulator a packed row wide, and a fourth scratch for the row's query
    operand (:func:`_heads_query`). The rule reads ``W`` alone."""
    _, H, Wp, hd = q.shape
    if W != 1:
        return _softmax_state(H, Wp, hd)
    G = -(-H // _HEADS)             # :func:`_whole_groups`
    return [*_softmax_state(G, _HEADS, 2 * hd),
            _vmem((G, _HEADS, 2 * hd), q.dtype)]


def _grid_spec(n_scalar, total, in_specs, out_specs, state):
    """``n_scalar`` counts the call's own scalar-prefetch operands; the
    schedule's three vectors go before them. ``state`` is the scratch
    (:func:`_softmax_state`, :func:`_window_state`)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + n_scalar, grid=(total,),
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=state)


#: scoped-VMEM ceiling handed to Mosaic. The compiler's default (16 MiB on
#: v5e) is below what a chunked-prefill window needs: the in-window score
#: block is (H, W, W) f32, and at W = prefill_chunk = 256, 12 heads, the int8
#: fused kernel's stack is 24.1 MiB (refused on the chip and by the described
#: compile in tests/test_chip_compile.py). A v5e core has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _compiler_params(interpret: bool):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    # the sweep carries loop state (online-softmax accumulators and the
    # write-on-index-change page outputs) — never parallelizable
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _row_map(s, row, *_):
    return (row[s], 0, 0, 0)


def _srow_map(s, row, *_):
    return (row[s], 0, 0)


def _page_map(s, row, pg, last, bt, *_):
    return (bt[row[s], pg[s]], 0, 0, 0)


def _scale_map(s, row, pg, last, bt, *_):
    return (bt[row[s], pg[s]], 0, 0)


def _write_page(s, row, pg, last, bt, pos_, wlo_, whi_):
    # pages outside the row's write range redirect to trash page 0:
    # Pallas only writes an output block back when its index CHANGES,
    # so the real page-pool writes stay O(1) per row per layer
    b, p = row[s], pg[s]
    inr = jnp.logical_and(p >= wlo_[b], p <= whi_[b])
    return jnp.where(inr, bt[b, p], 0)


def _write_map(s, *scalars):
    return (_write_page(s, *scalars), 0, 0, 0)


def _swrite_map(s, *scalars):
    return (_write_page(s, *scalars), 0, 0)


def _block_specs(q, kv_pages, scales):
    """``(row spec of q and the output, row spec of the packed window
    rows, page spec, [scale spec] * len(scales))`` of one call."""
    from jax.experimental import pallas as pl

    _, H, Wp, hd = q.shape
    Hkv, page = kv_pages.shape[1:3]     # H, but for grouped queries
    return (pl.BlockSpec((1, H, Wp, hd), _row_map),
            pl.BlockSpec((1, Hkv, Wp, 2 * hd), _row_map),
            pl.BlockSpec((1, Hkv, page, 2 * hd), _page_map),
            [pl.BlockSpec((1, Hkv, page), _scale_map)] * len(scales))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pa_read_call(q, kv_pages, block_tables, lengths, *scales,
                  scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = kv_pages.shape[2]
    *sweep, total = _schedule(lengths, -1, page, block_tables.shape[1])
    kernel = functools.partial(_pa_read_kernel, scale=scale, page=page,
                               quant=bool(scales))
    row, _, pages, scale_specs = _block_specs(q, kv_pages, scales)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(2, total, in_specs=[row, pages, *scale_specs],
                             out_specs=row,
                             state=_softmax_state(H, Wp, hd)),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(*sweep, block_tables, lengths, q, kv_pages, *scales)


@functools.partial(jax.jit,
                   static_argnames=("v_width", "scale", "interpret"))
def _pa_latent_call(q, kv_pages, block_tables, lengths, *, v_width, scale,
                    interpret):
    """The absorbed kernel over LATENT pages ``(N, 1, page, dk)``: one KV
    head whose row is the key and, in its first ``v_width`` values, the
    value; the kernel's window is the ``Hq`` query heads that share it, so
    one page DMA serves them all. The same ragged sweep, of BLOCKS of a
    row's pages: a grid step fetches and folds up to ``k`` consecutive pages
    of one row, so a long row pays a step's fixed cost once a block. ``k``
    is no argument: it follows from what the call can see
    (:func:`latent_block`), and a narrow table's short rows sweep page by
    page, the program they always were. A page of a row's last block that
    the row does not need is neither fetched nor folded: the trash page and
    stale table entries hold NaN on the chip, and a masked key's zero
    weight does not stop one (``0 x NaN``).

    The launch and the kernel's body are at the END of the file
    (:func:`_latent_launch`) and this function keeps its length: a compiled
    Pallas program's cache key holds the line numbers of the calls below.

    The name is the trace's: the cells read this kernel's seconds under
    ``jit_tick/_pa_latent_call``."""
    return _latent_launch(
        q, kv_pages, block_tables, lengths, v_width=v_width, scale=scale,
        interpret=interpret, k=latent_block(
            math.prod(kv_pages.shape[1:]) * kv_pages.dtype.itemsize,
            block_tables.shape[1]))


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_window_read_call(q, kv_new, kv_pages, block_tables, pos, *scales,
                         W, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = kv_pages.shape[2]
    # under the mesh mount this is a shard's own schedule, of its own rows
    *sweep, total = _schedule(pos, -1, page, block_tables.shape[1])
    kernel = functools.partial(_pa_window_kernel, scale=scale, page=page,
                               W=W, quant=bool(scales))
    row, new, pages, scale_specs = _block_specs(q, kv_pages, scales)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(2, total,
                             in_specs=[row, new, pages, *scale_specs],
                             out_specs=row, state=_window_state(q, W)),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(*sweep, block_tables, pos, q, kv_new, kv_pages, *scales)


def _fused_schedule(pos, wlo, whi, page, n_pages):
    """The sweep of a fused call: a row with an empty write range (an
    inactive one) has nothing to read either, and takes one step."""
    return _schedule(jnp.where(wlo <= whi, pos, 0), whi, page, n_pages)


def _fused_launch(q, kv_new, kv_pages, block_tables, pos, wlo, whi, *,
                  W, scale, interpret):
    """The fused launch both jitted names below make: ``q`` (B, H, Wp, hd)
    over a pool of ``Hkv`` heads, ``H // Hkv`` query heads a KV head."""
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    Hkv, page = kv_pages.shape[1:3]
    *sweep, total = _fused_schedule(pos, wlo, whi, page,
                                    block_tables.shape[1])
    kernel = functools.partial(_pa_fused_kernel, scale=scale, page=page, W=W,
                               share=H // Hkv)
    row, new, pages, _ = _block_specs(q, kv_pages, ())
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            4, total, in_specs=[row, new, pages],
            out_specs=[row, pl.BlockSpec((1, Hkv, page, 2 * hd), _write_map)],
            state=_window_state(q, W)),
        out_shape=[jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
                   jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype)],
        # operand indices COUNT the 7 scalar-prefetch args: the pool is
        # operand 9, aliased onto output 1 so it updates in place
        input_output_aliases={9: 1},
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(*sweep, block_tables, pos, wlo, whi, q, kv_new, kv_pages)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_fused_call(q, kv_new, kv_pages, block_tables, pos, wlo, whi, *,
                   W, scale, interpret):
    return _fused_launch(q, kv_new, kv_pages, block_tables, pos, wlo, whi,
                         W=W, scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pa_gqa_call(q, kv_new, kv_pages, block_tables, pos, wlo, whi, *,
                 scale, interpret):
    """The grouped-query decode call under its own name (a trace shows it
    apart from the dense block's): one query a head a row."""
    return _fused_launch(q, kv_new, kv_pages, block_tables, pos, wlo, whi,
                         W=1, scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_fused_call_q(q, kv_new, kvq_new, ks_new, vs_new, kv_pages, k_scale,
                     v_scale, block_tables, pos, wlo, whi, *,
                     W, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = kv_pages.shape[2]
    *sweep, total = _fused_schedule(pos, wlo, whi, page,
                                    block_tables.shape[1])
    kernel = functools.partial(_pa_fused_kernel_q, scale=scale, page=page,
                               W=W)
    row, new, pages, scale_specs = _block_specs(q, kv_pages,
                                                (k_scale, v_scale))
    srow = pl.BlockSpec((1, H, Wp), _srow_map)
    swrite = pl.BlockSpec((1, H, page), _swrite_map)
    scale_shape = jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            4, total,
            in_specs=[row, new, new, srow, srow, pages, *scale_specs],
            out_specs=[row, pl.BlockSpec((1, H, page, 2 * hd), _write_map),
                       swrite, swrite],
            state=_window_state(q, W)),
        out_shape=[jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
                   jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
                   scale_shape, scale_shape],
        # operand indices count the 7 scalar-prefetch args: the pool is
        # operand 12, its scale pools 13/14 — all three alias their
        # outputs so pages AND scales update in place through the same
        # trash-redirected write maps
        input_output_aliases={12: 1, 13: 2, 14: 3},
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(*sweep, block_tables, pos, wlo, whi, q, kv_new, kvq_new,
                ks_new, vs_new, kv_pages, k_scale, v_scale)


# ---- block selection (grouped-query sparse decode) --------------------------
# One query a row, and for each (row, KV head) a LIST of logical pages to
# attend: the blocks a sparse layer's scorer chose (models/zoo/hybrid.py)
# instead of the row's whole block table. A page block is ONE head's
# (1, 1, page, 2*hd) slice of a page, its physical index read through the
# block table from the scalar-prefetched list; the ``hg`` query heads that
# share the KV head are the kernel's window, so one page DMA serves them all.
# An entry below 0 is no page: it is not folded, and its DMA is the row's
# page 0. Read only: the engine writes the token's K/V row before it selects.
# Grid (rows, KV heads, BLOCKS of ``k`` listed pages), ``k`` page operands on
# the one pool (PR 46). The launch and the kernel are at the END of the file
# (:func:`_select_launch`) and this section keeps its length: a compiled
# Pallas program's cache key holds the line numbers of the calls below.

#: the most a grid step of the selected-block walk fetches. A head's slice of
#: a listed page is small (64 x 256 bf16: 32 KB, 0.04 us of the HBM's time
#: under a step's 0.45), so a walk pays its steps' fixed cost and the serial
#: chain of their online-softmax updates, not its bytes: eight such pages a
#: step (PERF.md, PR 46)
_SELECT_BLOCK_BYTES = 1 << 18


def select_block(page_bytes: int, n_sel: int) -> int:
    """Listed pages a grid step of the selected-block walk folds, from what
    a call can see of its shapes: the largest of 8, 4, 2, 1 that divides the
    list's ``n_sel`` entries and whose pages, ``page_bytes`` a head's slice
    of one, :data:`_SELECT_BLOCK_BYTES` hold. The host counts the walk by
    the same rule (``models/zoo/hybrid.py`` ``_SelectCounts``)."""
    return next(k for k in (8, 4, 2, 1) if n_sel % k == 0
                and (k == 1 or k * page_bytes <= _SELECT_BLOCK_BYTES))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pa_select_call(q, kv_pages, block_tables, sel, lengths, *,
                    scale, interpret):
    """The selected-block walk of ``q`` (B, G, hg, hd) over the ``sel``
    (B * G, n_sel) listed pages of each (row, KV head), in BLOCKS: a grid
    step fetches and folds ``k`` of the list's entries, so a walk pays a
    step's fixed cost and its serial chain once a block (MiniCPM-SALA's
    lists of 64 and 128 pages of 64 x 256 bf16: eight a step). ``k`` is no
    argument: it follows from what the call can see (:func:`select_block`),
    and a list that 2 does not divide walks page by page, the program it
    always was. A block whose entries are all pages is ONE online-softmax
    update; a block with an entry below 0 folds page by page, each page
    under its own guard: such an entry's operand holds the row's page 0,
    which for an idle row is the trash page, NaN on the chip, and a masked
    key's zero weight does not stop one (``0 x NaN``). Top-k lists the
    entries that are no block last: only a short row's last block.

    The name is the trace's: the hybrid cell reads this kernel's seconds
    under ``jit_tick/_pa_select_call``."""
    return _select_launch(
        q, kv_pages, block_tables, sel, lengths, scale=scale,
        interpret=interpret, k=select_block(
            math.prod(kv_pages.shape[2:]) * kv_pages.dtype.itemsize,
            sel.shape[1]))


def paged_attention_selected(q, kv_pages, block_tables, sel_pages, lengths,
                             *, scale: Optional[float] = None,
                             interpret: Optional[bool] = None):
    """Grouped-query decode attention over LISTED pages, read in place.

    ``q`` (B, G, hg, hd): one query a row, ``hg`` query heads for each of
    the ``G`` KV heads of the packed pool ``kv_pages`` (N, G, page, 2*hd).
    ``sel_pages`` (B, G, n) int32 holds, for each (row, KV head), the
    LOGICAL pages to attend (their order is free, an entry below 0 is
    skipped); they are mapped to physical pages through ``block_tables``
    (B, P). Keys at positions ``>= lengths[b]`` are masked, so the page that
    holds the row's newest token may be listed whole. A row with nothing
    listed yields zeros. Returns (B, G, hg, hd) in ``q.dtype``."""
    if interpret is None:
        interpret = _auto_interpret()
    B, G, hg, hd = q.shape
    if scale is None:
        scale = float(1.0 / math.sqrt(hd))
    return _pa_select_call(
        q, kv_pages, block_tables.astype(jnp.int32),
        sel_pages.reshape(B * G, -1).astype(jnp.int32),
        lengths.astype(jnp.int32), scale=scale, interpret=bool(interpret))


def paged_attention_latent(q, kv_pages, block_tables, lengths, *,
                           v_width: int, scale: float,
                           interpret: Optional[bool] = None):
    """Decode attention over LATENT pages, read in place: ``q`` (B, Hq, dk),
    one absorbed query a head a row, over the packed ``(N, 1, page, dk)``
    pool whose row a token is its key and, in the first ``v_width`` values,
    its value (multi-head latent attention's cache: every head reads the
    same row). Keys at positions ``>= lengths[b]`` are masked; a row with
    ``lengths[b] == 0`` yields zeros. The kernel's window is the heads, so
    a head count that is no multiple of the query dtype's sublane tile (20
    float32 heads: 8) goes in padded with zero queries up to the next one;
    their contexts are sliced off here and never stored, and a whole count
    (32) reaches the call as it is. Returns (B, Hq, v_width) in
    ``q.dtype``."""
    if interpret is None:
        interpret = _auto_interpret()
    Hq = q.shape[1]
    short = -Hq % sublane_multiple(q.dtype)
    if short:
        q = jnp.pad(q, ((0, 0), (0, short), (0, 0)))
    return _pa_latent_call(
        q[:, None], kv_pages, block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32), v_width=int(v_width), scale=float(scale),
        interpret=bool(interpret))[:, 0, :Hq]


# ---- mesh mount (shard_map) -------------------------------------------------

def _mount_specs(slot_axis, head_axis):
    """The per-shard partition specs of the mount, derived mechanically
    from the engine's cache layout (``continuous.py``): batch rows over
    ``slot_axis`` ("dp" or None), heads over ``head_axis`` ("tp" or
    None), page/lane dims never split."""
    from jax.sharding import PartitionSpec as P
    row = P(slot_axis, head_axis, None, None)     # q / k_new / v_new / out
    pool = P(None, head_axis, None, None)         # the K/V page pools
    return row, pool, P(slot_axis, None), P(slot_axis)


def _scale_mount_spec(head_axis):
    """Partition spec of the (N, H, page) scale pools under a mesh —
    heads over ``head_axis``, like the page pools they scale."""
    from jax.sharding import PartitionSpec as P
    return P(None, head_axis, None)


def _check_mount(mesh, B, H, slot_axis, head_axis):
    if head_axis is not None:
        tp = mesh.shape[head_axis]
        if H % tp:
            raise ValueError(
                f"heads {H} not divisible by mesh {head_axis}={tp}")
    if slot_axis is not None:
        dp = mesh.shape[slot_axis]
        if B % dp:
            raise ValueError(
                f"batch {B} not divisible by mesh {slot_axis}={dp}")


def _write_index(pool, W, block_tables, pos, active):
    """``(physical page, offset in page)`` of each of the ``B*W`` window
    positions ``pos[b] + j``, flattened row-major; inactive rows redirect
    to trash page 0, like every other writer."""
    page = pool.shape[2]
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)       # (B, W)
    phys = jnp.take_along_axis(block_tables, wpos // page, axis=1)
    if active is not None:
        phys = jnp.where(active[:, None], phys, 0)
    return phys.reshape(-1), (wpos % page).reshape(-1)


def _flat_rows(rows):
    """(B, H, W, d) window rows as (B*W, H, d), one row per position."""
    B, H, W, d = rows.shape
    return rows.transpose(0, 2, 1, 3).reshape(B * W, H, d)


def stored_kv(k, v, pool, k_scale=None, v_scale=None):
    """What ``pool`` stores for K and V rows ``(..., hd)``: ``(packed
    values,)``, or for a quantized pool ``(packed values, k scales, v
    scales)``, each row through :func:`quantize_kv` — the one helper every
    writer of pages shares, so their bytes agree bit for bit."""
    if k_scale is None:
        return (pack_kv(k, v).astype(pool.dtype),)
    (kq, ks), (vq, vs) = (quantize_kv(r, pool.dtype) for r in (k, v))
    return (pack_kv(kq, vq), ks.astype(k_scale.dtype),
            vs.astype(v_scale.dtype))


def _pool_write_rows(pool, k_rows, v_rows, block_tables, pos, active,
                     *scales):
    """Scatter each row's W fresh K/V rows into its pages — the mesh
    path's page write, OUTSIDE the shard_map mount. Plain ``.at[].set``
    indexing that GSPMD partitions on the untouched head axis, writing
    bytes bit-identical to ``transformer._paged_writeback`` and to the
    fused kernel's in-launch scatter (same index math: physical page via
    the block table, offset ``pos+j`` mod page; same :func:`stored_kv`).
    With the pool's two ``(N, H, page)`` scale pools as ``scales`` the
    rows are quantized and their per-head scales land at the same
    (physical page, offset). Returns ``(pool, *scales)`` updated."""
    pf, of = _write_index(pool, k_rows.shape[2], block_tables, pos, active)
    new = stored_kv(_flat_rows(k_rows), _flat_rows(v_rows), pool, *scales)
    return tuple(buf.at[pf, :, of].set(rows)
                 for buf, rows in zip((pool, *scales), new))


def _pad_window(t, Wp):
    """Zero-pad axis 2 (the window) of ``t`` up to ``Wp`` rows."""
    W = t.shape[2]
    if W == Wp:
        return t
    return jnp.pad(t, [(0, 0), (0, 0), (0, Wp - W)] +
                   [(0, 0)] * (t.ndim - 3))


def _mounted(call, mesh, slot_axis, head_axis, n_rows, n_scales):
    """``call(rows..., pool, block_tables, vector, scales...)`` mounted
    via ``shard_map``: heads over ``head_axis``, batch rows over
    ``slot_axis``, the pool replicated over slots."""
    from ..parallel.mesh import get_shard_map
    shard_map, unchecked = get_shard_map()
    row, pool, bt_spec, vec = _mount_specs(slot_axis, head_axis)
    return shard_map(
        call, mesh=mesh,
        in_specs=((row,) * n_rows + (pool, bt_spec, vec)
                  + (_scale_mount_spec(head_axis),) * n_scales),
        out_specs=row, **unchecked)


def paged_attention(q, kv_pages, block_tables, lengths, *,
                    k_scale=None, v_scale=None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    mesh=None, slot_axis: Optional[str] = None,
                    head_axis: Optional[str] = None):
    """Read-only paged attention: queries ``q`` (B, H, W, hd) attend the
    first ``lengths[b]`` cached keys of row ``b``, read in place from
    the packed ``(N, H, page, 2*hd)`` page pool (:func:`pack_kv`) through
    ``block_tables`` (B, P). A row with ``lengths[b] == 0`` yields zeros
    (the flash convention for fully-masked rows). Returns (B, H, W, hd)
    in ``q.dtype``.

    With ``k_scale``/``v_scale`` (the pool's ``(N, H, page)`` scale
    arrays) the pool holds QUANTIZED values: the scale blocks ride the
    same block-table index_map as their pages and the kernel dequantizes
    in VMEM — HBM only ever moves the quantized bytes.

    With ``mesh=`` the kernel is mounted via ``jax.shard_map``: heads
    split over ``head_axis`` (typically ``"tp"``) and rows optionally
    over ``slot_axis`` (``"dp"``); each shard runs the unchanged kernel
    over its head slice and the result carries the caller's row spec —
    no collective inside the mount."""
    if interpret is None:
        interpret = _auto_interpret()
    B, H, W, hd = q.shape
    if scale is None:
        scale = float(1.0 / math.sqrt(hd))
    qp = _pad_window(q, _round_up(W, sublane_multiple(q.dtype)))
    scales = () if k_scale is None else (k_scale, v_scale)
    call = functools.partial(_pa_read_call, scale=scale,
                             interpret=bool(interpret))
    if mesh is not None:
        _check_mount(mesh, B, H, slot_axis, head_axis)
        call = _mounted(call, mesh, slot_axis, head_axis, 1, len(scales))
    out = call(qp, kv_pages, block_tables.astype(jnp.int32),
               lengths.astype(jnp.int32), *scales)
    return out[:, :, :W]


def _write_range(pos, W, page, active):
    """``(first, last)`` logical page each row's ``W`` fresh rows land in."""
    wlo = pos // page
    whi = (pos + W - 1) // page
    if active is not None:
        # an empty write range (lo > hi): the index_map sends every page
        # of the row to trash and the overlay never fires
        wlo = jnp.where(active, wlo, 1)
        whi = jnp.where(active, whi, 0)
    return wlo.astype(jnp.int32), whi.astype(jnp.int32)


def paged_attention_gqa(q, k_new, v_new, kv_pages, block_tables, pos, *,
                        active=None, scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Fused GROUPED-QUERY decode attention + page scatter, one launch: the
    decode tick of a layer whose ``H`` query heads share ``Hkv`` KV heads.

    ``q`` (B, H, hd), one query a head a row at position ``pos[b]``;
    ``k_new`` / ``v_new`` (B, Hkv, hd) that token's fresh row; ``kv_pages``
    (N, Hkv, page, 2*hd) the packed pool. Query head ``h`` reads KV head
    ``h // (H // Hkv)``: the ``H // Hkv`` (a power of two up to 16) query
    heads of a KV head fold that head's page block, eight state rows a group
    under :func:`_fold_heads`' block-diagonal mask (16: two whole groups on
    one head, :func:`_fold_wide`). Sweep, scatter, ``active`` and ownership:
    :func:`paged_attention_window`'s at ``W == 1``; at ``H == Hkv`` the
    context is that call's, bit for bit. Returns ``(ctx (B, H, hd),
    kv_pages)``, the pool updated in place (aliased)."""
    if interpret is None:
        interpret = _auto_interpret()
    B, H, hd = q.shape
    Hkv, page = kv_pages.shape[1:3]
    share = H // max(Hkv, 1)
    if share * Hkv != H or 2 * _HEADS % share or k_new.shape[1] != Hkv:
        raise ValueError(
            f"{H} query heads over {Hkv} KV heads: a KV head serves 1, 2, 4, "
            f"{_HEADS} or {2 * _HEADS} query heads")
    if scale is None:
        scale = float(1.0 / math.sqrt(hd))
    pos = pos.astype(jnp.int32)
    Wp = sublane_multiple(q.dtype)
    out, pool = _pa_gqa_call(
        _pad_window(q[:, :, None], Wp),
        _pad_window(pack_kv(k_new, v_new)[:, :, None], Wp), kv_pages,
        block_tables.astype(jnp.int32), pos, *_write_range(pos, 1, page,
                                                           active),
        scale=scale, interpret=bool(interpret))
    return out[:, :, 0], pool


def paged_attention_window(q, k_new, v_new, kv_pages, block_tables, pos, *,
                           active=None, k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           mesh=None, slot_axis: Optional[str] = None,
                           head_axis: Optional[str] = None):
    """Fused decode-window attention + page scatter, one launch.

    Row ``b``'s W queries sit at absolute positions
    ``pos[b] .. pos[b]+W-1``; they attend every cached key strictly
    below ``pos[b]`` (read in place from the pool) plus the window's
    own keys ``k_new``/``v_new`` (B, H, W, hd) under the in-window
    causal mask, and the fresh K/V rows are scattered into their pages
    in the same launch. Rows where ``active`` is False neither write
    their pages (their writes redirect to trash page 0) nor produce
    meaningful context. Returns ``(ctx, kv_pages)`` with the pool
    buffer updated in place (aliased).

    With ``k_scale``/``v_scale`` (the ``(N, H, page)`` scale pools) the
    page pool holds QUANTIZED values: page reads dequantize in VMEM and
    the in-launch scatter writes each fresh row as the sanctioned
    :func:`~mmlspark_tpu.ops.kv_quant.quantize_kv` made it. The return
    grows to ``(ctx, kv_pages, k_scale, v_scale)`` — scales alias and
    update in place exactly like pages.

    With ``mesh=`` the attention mounts via ``jax.shard_map`` (heads
    over ``head_axis``, rows optionally over ``slot_axis``) in
    READ-ONLY form, and the fresh rows are scattered by
    :func:`_pool_write_rows` outside
    the mount — the written bytes are bit-identical to the fused
    in-kernel scatter, so single-chip and mesh engines produce the same
    pages."""
    if interpret is None:
        interpret = _auto_interpret()
    B, H, W, hd = q.shape
    page = kv_pages.shape[2]
    if scale is None:
        scale = float(1.0 / math.sqrt(hd))
    pos = pos.astype(jnp.int32)
    Wp = _round_up(W, sublane_multiple(q.dtype))
    bt = block_tables.astype(jnp.int32)
    scales = () if k_scale is None else (k_scale, v_scale)
    qp, kvn = _pad_window(q, Wp), _pad_window(pack_kv(k_new, v_new), Wp)
    if mesh is not None:
        _check_mount(mesh, B, H, slot_axis, head_axis)
        call = functools.partial(_pa_window_read_call, W=W, scale=scale,
                                 interpret=bool(interpret))
        ctx = _mounted(call, mesh, slot_axis, head_axis, 2, len(scales))(
            qp, kvn, kv_pages, bt, pos, *scales)
        return (ctx[:, :, :W], *_pool_write_rows(
            kv_pages, k_new, v_new, bt, pos, active, *scales))
    wlo, whi = _write_range(pos, W, page, active)
    if scales:
        out, *pools = _pa_fused_call_q(
            qp, kvn, *(_pad_window(t, Wp) for t in stored_kv(
                k_new, v_new, kv_pages, *scales)),
            kv_pages, *scales, bt, pos, wlo, whi,
            W=W, scale=scale, interpret=bool(interpret))
    else:
        out, *pools = _pa_fused_call(qp, kvn, kv_pages, bt, pos, wlo, whi,
                                     W=W, scale=scale,
                                     interpret=bool(interpret))
    return (out[:, :, :W], *pools)


# ---- the absorbed latent kernel: a BLOCK of a row's pages a grid step -------
# Here, below every other call, so that their line numbers stand
# (:func:`_pa_latent_call`).

#: the most a grid step of the latent sweep fetches: four pages of 256 rows
#: of 640 bf16 values. A step's fixed cost and its serial chain are paid once
#: a block; past a megabyte or so a block more buys little (PERF.md, PR 44)
_LATENT_BLOCK_BYTES = 5 << 18

#: the fewest blocks a slot's block table holds. The page rule serves
#: sixteen pages a slot and holds a page to 256 tokens
#: (``serving/continuous.py`` ``derived_page_size``); where that cap leaves
#: a slot more pages than sixteen its rows are long and the sweep takes up
#: the slack in blocks, and a slot of sixteen pages keeps them one a step
_LATENT_BLOCKS_A_SLOT = 16


def latent_block(page_bytes: int, pages_a_slot: int) -> int:
    """Pages a grid step of the latent sweep folds, from what a call can
    see of its shapes: as many as :data:`_LATENT_BLOCK_BYTES` hold, a slot's
    block table at least :data:`_LATENT_BLOCKS_A_SLOT` blocks wide, at least
    one. The host counts the sweep by the same rule
    (``models/zoo/hybrid.py`` ``_LatentCounts``)."""
    return max(1, min(_LATENT_BLOCK_BYTES // page_bytes,
                      pages_a_slot // _LATENT_BLOCKS_A_SLOT))


def _block_holds(row_of, blk_of, lengths, page, n_pages, k):
    """What each of a block's ``k`` page operands HOLDS at each step of the
    block sweep, ``(k, steps)`` int32 of ``row * n_pages + page``: the page
    ``k * blk + j`` of the step's row where the row needs it (it holds a
    key below the row's length), else what the operand held a step before.
    The pipeline fetches an operand's block when its index CHANGES, so a
    page no row needs is never moved: a two-page row moves two pages. Before
    the first step that needs it an operand holds row 0's first page."""
    B = lengths.shape[0]
    need_of = jnp.minimum(-(-lengths // page), n_pages)       # pages a row
    mine = row_of[:, None] == jnp.arange(B, dtype=jnp.int32)[None, :]
    needs = jnp.sum(jnp.where(mine, need_of[None, :], 0), axis=1)
    p = k * blk_of[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]
    flat = jnp.where(p < needs[None, :], row_of[None, :] * n_pages + p, -1)
    return jnp.maximum(jax.lax.cummax(flat, axis=1), 0).astype(jnp.int32)


def _held_page_map(j, n_pages):
    """The index map of a block's page operand ``j``: the physical page of
    what :func:`_block_holds` says it holds at step ``s``."""
    def index_map(s, row, blk, last, bt, lengths, *holds):
        f = holds[j][s]
        return (bt[jax.lax.div(f, n_pages), jax.lax.rem(f, n_pages)], 0, 0, 0)
    return index_map


def _block_fold(m_scr, l_scr, acc_scr, q, pages, first, bound, scale, page,
                v_width):
    """Fold a WHOLE block, the row's pages ``first ..`` as ``pages`` (a list
    of ``(1, page, dk)`` latent pages, every one needed), in ONE online
    softmax update: the pages' scores side by side, one max, exp and sum
    over the block's keys, the pages' value products added up. The
    mathematics of a fold a page; the order of its float32 sums is another,
    and the chain a step waits for is paid once a block."""
    s = jnp.concatenate([_scores(q, kv, scale) for kv in pages], axis=-1)
    t = first * page + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, s.shape[-1]), 2)
    _fold(m_scr, l_scr, acc_scr, s, t < bound, lambda p: sum(
        _weigh(p[..., j * page:(j + 1) * page], kv[..., :v_width])
        for j, kv in enumerate(pages)))


def _pa_latent_kernel(row_ref, blk_ref, last_ref, bt_ref, len_ref, *rest,
                      scale, page, v_width, k):
    """One grid step = block ``blk`` of row ``b``: the row's pages
    ``k * blk .. k * blk + k - 1``, each its own operand on the pool. A block
    the row needs whole is one fold (:func:`_block_fold`). In a row's last
    block a page it does not need was not fetched (:func:`_block_holds`: the
    operand holds an older page, or on the chip NaN) and must not be folded
    either, whatever its keys' mask: that block folds page by page, each
    page under its own guard, which at ``k`` = 1 is all there is (the
    one-page sweep, its program unchanged)."""
    from jax.experimental import pallas as pl

    rest = rest[k if k > 1 else 0:]     # past the holds: the index maps'
    q_ref, pages, o_ref = rest[0], rest[1:1 + k], rest[1 + k]
    state = rest[2 + k:]                # m, l, the accumulator
    b, blk, last = _step(row_ref, blk_ref, last_ref)
    pl.when(blk == 0)(lambda: _init(*state))
    bound = len_ref[b]

    def page_by_page():
        for j, kv_ref in enumerate(pages):
            p = blk if k == 1 else blk * k + j

            @pl.when(p * page < bound)
            def _page(kv_ref=kv_ref, p=p):
                _pages_fold(*state, q_ref[0], kv_ref[0], p, bound, scale,
                            page, v_width)

    if k == 1:
        page_by_page()
    else:
        whole = (blk * k + k - 1) * page < bound
        pl.when(whole)(lambda: _block_fold(
            *state, q_ref[0], [ref[0] for ref in pages], blk * k, bound,
            scale, page, v_width))
        pl.when(jnp.logical_not(whole))(page_by_page)
    pl.when(last)(lambda: _finalize(o_ref, state[1], state[2]))


def _latent_launch(q, kv_pages, block_tables, lengths, *, v_width, scale,
                   interpret, k):
    """The launch :func:`_pa_latent_call` makes, ``k`` pages a grid step."""
    from jax.experimental import pallas as pl

    B, _, Hq, dk = q.shape
    page, n_pages = kv_pages.shape[2], block_tables.shape[1]
    # a block is a page of k * page keys: the same sweep, of blocks
    row_of, blk_of, last_of, total = _schedule(lengths, -1, k * page,
                                               -(-n_pages // k))
    if k == 1:
        # every step needs its one page: the operand holds what the step says
        holds, maps = (), [_page_map]
    else:
        holds = tuple(_block_holds(row_of, blk_of, lengths, page, n_pages, k))
        maps = [_held_page_map(j, n_pages) for j in range(k)]
    kernel = functools.partial(_pa_latent_kernel, scale=scale, page=page,
                               v_width=v_width, k=k)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            2 + len(holds), total,
            in_specs=[pl.BlockSpec((1, 1, Hq, dk), _row_map),
                      *(pl.BlockSpec((1, 1, page, dk), m) for m in maps)],
            out_specs=pl.BlockSpec((1, 1, Hq, v_width), _row_map),
            state=_softmax_state(1, Hq, v_width)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Hq, v_width), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(row_of, blk_of, last_of, block_tables, lengths, *holds, q,
                *[kv_pages] * k)


# ---- a KV head that serves two groups of query heads ---------------------------

def _fold_wide(m_scr, l_scr, acc_scr, q, kv, n, scale, share):
    """:func:`_fold_heads` where a KV head serves ``share`` = a whole number
    of groups of query heads (16: two groups): the ``share`` state rows of
    KV head ``j`` are the ``share // _HEADS`` consecutive groups ``j *
    share // _HEADS ..``, so the query operand and the weights regroup, in
    float32 whose register holds a group's rows (free reshapes), to ``share``
    rows a KV head and meet that head's ``K`` keys in ONE product: no mask
    between heads, the first ``n`` keys alone."""
    Hkv, K, width = kv.shape
    G = q.shape[0]

    def by_kv_head(t):      # (G, _HEADS, .) -> (Hkv, share, .)
        return t.astype(jnp.float32).reshape(Hkv, share, t.shape[-1])

    s = _scores(by_kv_head(q).astype(q.dtype), kv, scale).reshape(
        G, _HEADS, K)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, K), 2)
    _fold(m_scr, l_scr, acc_scr, s, col < jnp.clip(n, 0, K),
          lambda p: _weigh(by_kv_head(p), kv).reshape(G, _HEADS, width))


# ---- the selected-block kernel: a BLOCK of listed pages a grid step ---------
# Here, at the end, so that the calls above keep their line numbers
# (:func:`_pa_select_call`).

def _entry(j, i, k):
    """The list column of entry ``i`` of block ``j``; at ``k`` = 1 the step
    itself, with no arithmetic, so that the one-page walk's text stands."""
    return j if k == 1 else j * k + i


def _select_fold(m_scr, l_scr, acc_scr, q, pages, lps, bound, scale, page):
    """Fold a WHOLE block, the listed pages ``lps`` (scalars, every one a
    page) as ``pages`` (their ``(1, page, 2*hd)`` blocks), in ONE online
    softmax update: the pages' rows one after another are one run of
    ``k * page`` keys, so one product gives the block's scores, one max, exp
    and sum go over them and one product weighs the values. The mathematics
    of a fold a page; the order of its float32 sums is another, and the
    chain a step waits for is paid once a block."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, len(lps) * page), 2)
    # page i's keys are columns i * page ..: key c's position is
    # lps[i] * page + c - i * page
    first = lps[0] * page
    for i, lp in enumerate(lps[1:], 1):
        first = jnp.where(col >= i * page, (lp - i) * page, first)
    _fold_keys(m_scr, l_scr, acc_scr, q, jnp.concatenate(pages, axis=1),
               first + col < bound, scale)


def _pa_select_kernel(bt_ref, sel_ref, len_ref, q_ref, *rest, scale, page,
                      n_blk, G, k):
    """One grid step = block ``j`` of the list of (row ``b``, KV head ``g``):
    its entries ``k * j .. k * j + k - 1``, each its own operand on the pool.
    A block whose entries are all pages is one fold (:func:`_select_fold`);
    one with an entry below 0 folds page by page, each page under its own
    guard, which at ``k`` = 1 is all there is (the one-page walk, its
    program unchanged)."""
    from jax.experimental import pallas as pl

    pages, o_ref, state = rest[:k], rest[k], rest[k + 1:]
    b, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pl.when(j == 0)(lambda: _init(*state))
    lps = [sel_ref[b * G + g, _entry(j, i, k)] for i in range(k)]

    def page_by_page():
        for lp, kv_ref in zip(lps, pages):
            @pl.when(lp >= 0)
            def _page(lp=lp, kv_ref=kv_ref):
                # the blocks are one head's: H = 1, the window the hg heads
                _pages_fold(*state, q_ref[0], _page_kv(kv_ref), lp,
                            len_ref[b], scale, page)

    if k == 1:
        page_by_page()
    else:
        whole = functools.reduce(jnp.minimum, lps) >= 0
        pl.when(whole)(lambda: _select_fold(
            *state, q_ref[0], [_page_kv(ref) for ref in pages], lps,
            len_ref[b], scale, page))
        pl.when(jnp.logical_not(whole))(page_by_page)
    pl.when(j == n_blk - 1)(lambda: _finalize(o_ref, state[1], state[2]))


def _select_launch(q, kv_pages, block_tables, sel, lengths, *, scale,
                   interpret, k):
    """The launch :func:`_pa_select_call` makes, ``k`` listed pages a grid
    step (``k`` divides the list)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, hg, hd = q.shape
    page, n_sel = kv_pages.shape[2], sel.shape[1]
    kernel = functools.partial(_pa_select_kernel, scale=scale, page=page,
                               n_blk=n_sel // k, G=G, k=k)
    row = pl.BlockSpec((1, 1, hg, hd), lambda b, g, j, *_: (b, g, 0, 0))

    def page_of(i):
        def index_map(b, g, j, bt, sel_, *_):
            lp = sel_[b * G + g, _entry(j, i, k)]
            return (bt[b, jnp.maximum(lp, 0)], g, 0, 0)
        return index_map

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, G, n_sel // k),
            in_specs=[row, *(pl.BlockSpec((1, 1, page, 2 * hd), page_of(i))
                             for i in range(k))],
            out_specs=row,
            scratch_shapes=_softmax_state(1, hg, hd)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret)
    return call(block_tables, sel, lengths, q, *[kv_pages] * k)
