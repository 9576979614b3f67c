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

* grid = (B, P_max) with the page sweep innermost. Blocks carry the full
  head dimension — a page block is ``(1, H, page, hd)`` — so each page is
  DMA'd ONCE per row per layer, not once per head.
* the physical page for grid step ``(b, p)`` comes from a
  scalar-prefetched block table: the BlockSpec index_map reads
  ``bt[b, p]`` (``PrefetchScalarGridSpec``), which is exactly the
  indirection ``paged_gather`` used to materialize. Unallocated logical
  pages map to the TRASH page 0 in the table; their keys are masked out
  by the per-row length bound anyway.
* running ``m``/``l`` live in VMEM scratch shaped ``(H, W, LANE)``
  (lane-replicated, as in ``flash_attention.py``); the f32 context
  accumulator is ``(H, W, hd)``. Masked logits use ``-1e30`` — a fully
  masked row yields ``l == 0`` and the final divide guards it to zeros
  rather than NaN.
* the FUSED variant (:func:`paged_attention_window`) also scatters the
  window's fresh K/V rows into their pages in the same launch, replacing
  the separate per-tick writeback. The window rows ride along as direct
  ``(B, H, W, hd)`` inputs folded into the online softmax under an
  in-window causal mask, so pages only ever supply keys strictly before
  ``pos[b]`` — reading each page's *pre-scatter* content is therefore
  exact. The scatter itself goes through ``input_output_aliases``: the
  page-pool outputs alias the inputs and their index_map redirects every
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
``(1, H, page, hd)`` block, so on a real TPU ``page_size`` must be a
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

__all__ = ["paged_attention", "paged_attention_window", "resolve_impl",
           "sublane_multiple", "aligned_page_size"]

_NEG = -1e30

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
    divide into for the kernel's ``(1, H, page, hd)`` page blocks."""
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


def _fold(m_scr, l_scr, acc_scr, s, valid, v):
    """One online-softmax update: fold the score block ``s`` (H, W, K)
    with key-validity ``valid`` (broadcastable) and values ``v``
    (H, K, hd) into the running (m, l, acc) VMEM state."""
    s = jnp.where(valid, s, _NEG)
    m_prev = m_scr[..., 0:1]                           # (H, W, 1)
    l_prev = l_scr[..., 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # `valid` (not the _NEG sentinel) zeroes masked probabilities: for a
    # row with every key masked so far, m_new == _NEG and exp(s - m_new)
    # would be exp(0) == 1 on the masked entries.
    p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)                      # <= 1
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)             # (H, W, hd)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _finalize(o_ref, l_scr, acc_scr):
    l = l_scr[..., 0:1]
    o_ref[0] = (acc_scr[...] /
                jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _page_scores(q, kp_ref, scale):
    kp = kp_ref[0].astype(jnp.float32)                  # (H, page, hd)
    return jax.lax.dot_general(
        q, kp, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (H, W, page)


def _deq_block(p_ref, s_ref):
    """Dequantize one (1, H, page, hd) page block with its (1, H, page)
    scale block — the IN-KERNEL dequant: both blocks arrived through the
    same block-table index_map, so this multiply happens in VMEM right
    after the page DMA and the quantized bytes are all HBM ever moves."""
    return (p_ref[0].astype(jnp.float32) *
            s_ref[0].astype(jnp.float32)[:, :, None])   # (H, page, hd)


def _page_scores_q(q, kp_ref, ks_ref, scale):
    return jax.lax.dot_general(
        q, _deq_block(kp_ref, ks_ref), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (H, W, page)


def _pa_read_kernel(bt_ref, len_ref, q_ref, kp_ref, vp_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, scale, page, n_pages):
    """One (b, p) grid step of the read-only page sweep: attend the
    queries over page ``p``'s keys, bounded by ``len_ref[b]``."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bound = len_ref[b]

    @pl.when(p * page < bound)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (H, W, hd)
        s = _page_scores(q, kp_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < bound,
              vp_ref[0].astype(jnp.float32))

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


def _pa_fused_kernel(bt_ref, pos_ref, wlo_ref, whi_ref, q_ref, kn_ref,
                     vn_ref, kp_ref, vp_ref, o_ref, ko_ref, vo_ref,
                     m_scr, l_scr, acc_scr, *, scale, page, W, n_pages):
    """One (b, p) grid step of the fused decode-window sweep.

    Page keys are masked STRICTLY below ``pos[b]`` — the window's own
    rows arrive as the direct (H, W, hd) ``kn``/``vn`` inputs, folded
    once at p == 0 under the in-window causal mask, so the page blocks
    are always read pre-scatter. Pages inside the row's write range get
    their fresh rows overlaid and written back through the aliased
    page-pool outputs; every other grid step leaves its (trash-directed)
    output block untouched."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[b]
    Wp = q_ref.shape[2]

    @pl.when(p == 0)
    def _init_and_window():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[0].astype(jnp.float32)                # (H, Wp, hd)
        kn = kn_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kn, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (H, Wp, Wp)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 2)
        # query j sees window keys j' <= j; padding key rows never
        # (padding QUERY rows keep every real key — they need a nonzero
        # denominator and their output is sliced off host-side)
        valid = jnp.logical_and(
            jnp.logical_or(col <= row, row >= W), col < W)
        _fold(m_scr, l_scr, acc_scr, s, valid,
              vn_ref[0].astype(jnp.float32))

    @pl.when(p * page < pos)
    def _pages():
        q = q_ref[0].astype(jnp.float32)
        s = _page_scores(q, kp_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < pos,
              vp_ref[0].astype(jnp.float32))

    in_write_range = jnp.logical_and(p >= wlo_ref[b], p <= whi_ref[b])

    @pl.when(in_write_range)
    def _scatter():
        # overlay the window rows that land in THIS page, in the pool
        # dtype (no f32 round-trip: the written bytes are bit-identical
        # to _paged_writeback's)
        kblk = kp_ref[0]                                # (H, page, hd)
        vblk = vp_ref[0]
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
        for j in range(W):                              # W static, small
            tgt = pos + j - p * page
            hit = ridx == tgt                           # all-False if out
            kblk = jnp.where(hit, kn_ref[0, :, j:j + 1, :], kblk)
            vblk = jnp.where(hit, vn_ref[0, :, j:j + 1, :], vblk)
        ko_ref[0] = kblk
        vo_ref[0] = vblk

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


def _pa_window_kernel(bt_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_ref,
                      vp_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale, page, W, n_pages):
    """One (b, p) grid step of the READ-ONLY decode-window sweep — the
    shard_map-mounted variant. Identical online-softmax math to
    :func:`_pa_fused_kernel` (window rows folded once at p == 0 under
    the in-window causal mask, pages masked strictly below ``pos[b]``),
    minus the in-kernel page scatter: under a mesh the fresh rows are
    written outside the mount (:func:`_pool_write_rows`), so only two
    scalar-prefetch operands (block table, pos) remain and no output
    aliases the pool."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[b]
    Wp = q_ref.shape[2]

    @pl.when(p == 0)
    def _init_and_window():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[0].astype(jnp.float32)                # (H, Wp, hd)
        kn = kn_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kn, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (H, Wp, Wp)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 2)
        valid = jnp.logical_and(
            jnp.logical_or(col <= row, row >= W), col < W)
        _fold(m_scr, l_scr, acc_scr, s, valid,
              vn_ref[0].astype(jnp.float32))

    @pl.when(p * page < pos)
    def _pages():
        q = q_ref[0].astype(jnp.float32)
        s = _page_scores(q, kp_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < pos,
              vp_ref[0].astype(jnp.float32))

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


# ---- quantized kernels ------------------------------------------------------
#
# Same grid, same online-softmax state, same masks as the bf16 kernels
# above — the only differences are (a) two extra (1, H, page) scale
# blocks riding the SAME block-table index_map as their page blocks,
# dequantized in VMEM by _deq_block before the dot, and (b) the fused
# variant's in-kernel scatter quantizing each window row through
# quantize_kv (the sanctioned helper — bit-identical to what
# _pool_write_rows/_paged_writeback write, so every writer agrees).

def _pa_read_kernel_q(bt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref,
                      vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale, page, n_pages):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    bound = len_ref[b]

    @pl.when(p * page < bound)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (H, W, hd)
        s = _page_scores_q(q, kp_ref, ks_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < bound,
              _deq_block(vp_ref, vs_ref))

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


def _window_fold(m_scr, l_scr, acc_scr, q_ref, kn_ref, vn_ref, scale, W):
    """The p == 0 window fold shared by the fused/window kernels: fresh
    rows arrive unquantized (they are direct inputs, not pages), folded
    under the in-window causal mask."""
    Wp = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32)                    # (H, Wp, hd)
    kn = kn_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, kn, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (H, Wp, Wp)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, Wp, Wp), 2)
    valid = jnp.logical_and(
        jnp.logical_or(col <= row, row >= W), col < W)
    _fold(m_scr, l_scr, acc_scr, s, valid,
          vn_ref[0].astype(jnp.float32))


def _pa_fused_kernel_q(bt_ref, pos_ref, wlo_ref, whi_ref, q_ref, kn_ref,
                       vn_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
                       ko_ref, vo_ref, kso_ref, vso_ref,
                       m_scr, l_scr, acc_scr, *, scale, page, W, n_pages):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(p == 0)
    def _init_and_window():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _window_fold(m_scr, l_scr, acc_scr, q_ref, kn_ref, vn_ref,
                     scale, W)

    @pl.when(p * page < pos)
    def _pages():
        q = q_ref[0].astype(jnp.float32)
        s = _page_scores_q(q, kp_ref, ks_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < pos,
              _deq_block(vp_ref, vs_ref))

    in_write_range = jnp.logical_and(p >= wlo_ref[b], p <= whi_ref[b])

    @pl.when(in_write_range)
    def _scatter():
        kblk = kp_ref[0]                                # (H, page, hd)
        vblk = vp_ref[0]
        ksblk = ks_ref[0]                               # (H, page)
        vsblk = vs_ref[0]
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
        sidx = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        for j in range(W):                              # W static, small
            tgt = pos + j - p * page
            hit = ridx == tgt                           # all-False if out
            shit = sidx == tgt
            kq, ksc = quantize_kv(kn_ref[0, :, j, :], kblk.dtype)
            vq, vsc = quantize_kv(vn_ref[0, :, j, :], vblk.dtype)
            kblk = jnp.where(hit, kq[:, None, :], kblk)
            vblk = jnp.where(hit, vq[:, None, :], vblk)
            ksblk = jnp.where(shit, ksc[:, None].astype(ksblk.dtype), ksblk)
            vsblk = jnp.where(shit, vsc[:, None].astype(vsblk.dtype), vsblk)
        ko_ref[0] = kblk
        vo_ref[0] = vblk
        kso_ref[0] = ksblk
        vso_ref[0] = vsblk

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


def _pa_window_kernel_q(bt_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_ref,
                        vp_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
                        acc_scr, *, scale, page, W, n_pages):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(p == 0)
    def _init_and_window():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _window_fold(m_scr, l_scr, acc_scr, q_ref, kn_ref, vn_ref,
                     scale, W)

    @pl.when(p * page < pos)
    def _pages():
        q = q_ref[0].astype(jnp.float32)
        s = _page_scores_q(q, kp_ref, ks_ref, scale)
        t = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page), 2)
        _fold(m_scr, l_scr, acc_scr, s, t < pos,
              _deq_block(vp_ref, vs_ref))

    @pl.when(p == n_pages - 1)
    def _fin():
        _finalize(o_ref, l_scr, acc_scr)


def _grid_spec(n_scalar, B, n_pages, in_specs, out_specs, H, Wp, hd):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar, grid=(B, n_pages),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[
            _vmem((H, Wp, _LANE), jnp.float32),   # running max m
            _vmem((H, Wp, _LANE), jnp.float32),   # running denominator l
            _vmem((H, Wp, hd), jnp.float32),      # f32 context accumulator
        ])


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
    # both grid dims carry loop state (online-softmax accumulators and
    # the write-on-index-change page outputs) — never parallelizable
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pa_read_call(q, k_pages, v_pages, block_tables, lengths, *,
                  scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_read_kernel, scale=scale, page=page,
                               n_pages=n_pages)

    def _q_map(b, p, bt, lens):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, lens):
        return (bt[b, p], 0, 0, 0)

    def _o_map(b, p, bt, lens):
        return (b, 0, 0, 0)

    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            2, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _q_map),
                pl.BlockSpec((1, H, page, hd), _page_map),
                pl.BlockSpec((1, H, page, hd), _page_map),
            ],
            out_specs=pl.BlockSpec((1, H, Wp, hd), _o_map),
            H=H, Wp=Wp, hd=hd),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, lengths, q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_fused_call(q, k_new, v_new, k_pages, v_pages, block_tables,
                   pos, wlo, whi, *, W, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_fused_kernel, scale=scale, page=page,
                               W=W, n_pages=n_pages)

    def _row_map(b, p, bt, pos_, wlo_, whi_):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, pos_, wlo_, whi_):
        return (bt[b, p], 0, 0, 0)

    def _write_map(b, p, bt, pos_, wlo_, whi_):
        # pages outside the row's write range redirect to trash page 0:
        # Pallas only writes an output block back when its index CHANGES,
        # so the real page-pool writes stay O(1) per row per layer
        inr = jnp.logical_and(p >= wlo_[b], p <= whi_[b])
        return (jnp.where(inr, bt[b, p], 0), 0, 0, 0)

    pool_shape = jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            4, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),   # q
                pl.BlockSpec((1, H, Wp, hd), _row_map),   # k_new
                pl.BlockSpec((1, H, Wp, hd), _row_map),   # v_new
                pl.BlockSpec((1, H, page, hd), _page_map),  # k pages
                pl.BlockSpec((1, H, page, hd), _page_map),  # v pages
            ],
            out_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),
                pl.BlockSpec((1, H, page, hd), _write_map),
                pl.BlockSpec((1, H, page, hd), _write_map),
            ],
            H=H, Wp=Wp, hd=hd),
        out_shape=[jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
                   pool_shape, pool_shape],
        # operand indices COUNT the 4 scalar-prefetch args: k_pages is
        # operand 7, v_pages operand 8 — aliased onto outputs 1/2 so the
        # pool updates in place
        input_output_aliases={7: 1, 8: 2},
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, pos, wlo, whi, q, k_new, v_new,
                k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_window_read_call(q, k_new, v_new, k_pages, v_pages, block_tables,
                         pos, *, W, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_window_kernel, scale=scale, page=page,
                               W=W, n_pages=n_pages)

    def _row_map(b, p, bt, pos_):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, pos_):
        return (bt[b, p], 0, 0, 0)

    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            2, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # q
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # k_new
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # v_new
                pl.BlockSpec((1, H, page, hd), _page_map),  # k pages
                pl.BlockSpec((1, H, page, hd), _page_map),  # v pages
            ],
            out_specs=pl.BlockSpec((1, H, Wp, hd), _row_map),
            H=H, Wp=Wp, hd=hd),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, pos, q, k_new, v_new, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pa_read_call_q(q, k_pages, v_pages, k_scale, v_scale, block_tables,
                    lengths, *, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_read_kernel_q, scale=scale, page=page,
                               n_pages=n_pages)

    def _q_map(b, p, bt, lens):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, lens):
        return (bt[b, p], 0, 0, 0)

    def _scale_map(b, p, bt, lens):
        return (bt[b, p], 0, 0)

    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            2, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _q_map),
                pl.BlockSpec((1, H, page, hd), _page_map),
                pl.BlockSpec((1, H, page, hd), _page_map),
                pl.BlockSpec((1, H, page), _scale_map),
                pl.BlockSpec((1, H, page), _scale_map),
            ],
            out_specs=pl.BlockSpec((1, H, Wp, hd), _q_map),
            H=H, Wp=Wp, hd=hd),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, lengths, q, k_pages, v_pages,
                k_scale, v_scale)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_fused_call_q(q, k_new, v_new, k_pages, v_pages, k_scale, v_scale,
                     block_tables, pos, wlo, whi, *, W, scale, interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_fused_kernel_q, scale=scale, page=page,
                               W=W, n_pages=n_pages)

    def _row_map(b, p, bt, pos_, wlo_, whi_):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, pos_, wlo_, whi_):
        return (bt[b, p], 0, 0, 0)

    def _scale_map(b, p, bt, pos_, wlo_, whi_):
        return (bt[b, p], 0, 0)

    def _write_map(b, p, bt, pos_, wlo_, whi_):
        inr = jnp.logical_and(p >= wlo_[b], p <= whi_[b])
        return (jnp.where(inr, bt[b, p], 0), 0, 0, 0)

    def _swrite_map(b, p, bt, pos_, wlo_, whi_):
        inr = jnp.logical_and(p >= wlo_[b], p <= whi_[b])
        return (jnp.where(inr, bt[b, p], 0), 0, 0)

    pool_shape = jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype)
    scale_shape = jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype)
    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            4, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # q
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # k_new
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # v_new
                pl.BlockSpec((1, H, page, hd), _page_map),  # k pages
                pl.BlockSpec((1, H, page, hd), _page_map),  # v pages
                pl.BlockSpec((1, H, page), _scale_map),     # k scales
                pl.BlockSpec((1, H, page), _scale_map),     # v scales
            ],
            out_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),
                pl.BlockSpec((1, H, page, hd), _write_map),
                pl.BlockSpec((1, H, page, hd), _write_map),
                pl.BlockSpec((1, H, page), _swrite_map),
                pl.BlockSpec((1, H, page), _swrite_map),
            ],
            H=H, Wp=Wp, hd=hd),
        out_shape=[jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
                   pool_shape, pool_shape, scale_shape, scale_shape],
        # operand indices count the 4 scalar-prefetch args: k/v pages are
        # operands 7/8, their scale pools 9/10 — all four alias their
        # outputs so pages AND scales update in place through the same
        # trash-redirected write maps
        input_output_aliases={7: 1, 8: 2, 9: 3, 10: 4},
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, pos, wlo, whi, q, k_new, v_new,
                k_pages, v_pages, k_scale, v_scale)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def _pa_window_read_call_q(q, k_new, v_new, k_pages, v_pages, k_scale,
                           v_scale, block_tables, pos, *, W, scale,
                           interpret):
    from jax.experimental import pallas as pl

    B, H, Wp, hd = q.shape
    page = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    kernel = functools.partial(_pa_window_kernel_q, scale=scale, page=page,
                               W=W, n_pages=n_pages)

    def _row_map(b, p, bt, pos_):
        return (b, 0, 0, 0)

    def _page_map(b, p, bt, pos_):
        return (bt[b, p], 0, 0, 0)

    def _scale_map(b, p, bt, pos_):
        return (bt[b, p], 0, 0)

    call = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            2, B, n_pages,
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # q
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # k_new
                pl.BlockSpec((1, H, Wp, hd), _row_map),     # v_new
                pl.BlockSpec((1, H, page, hd), _page_map),  # k pages
                pl.BlockSpec((1, H, page, hd), _page_map),  # v pages
                pl.BlockSpec((1, H, page), _scale_map),     # k scales
                pl.BlockSpec((1, H, page), _scale_map),     # v scales
            ],
            out_specs=pl.BlockSpec((1, H, Wp, hd), _row_map),
            H=H, Wp=Wp, hd=hd),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    return call(block_tables, pos, q, k_new, v_new, k_pages, v_pages,
                k_scale, v_scale)


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


def _pool_write_rows(pool, rows, block_tables, pos, active):
    """Scatter each row's W fresh K/V rows into its pages — the mesh
    path's page write, OUTSIDE the shard_map mount. Plain ``.at[].set``
    indexing that GSPMD partitions on the untouched head axis, writing
    bytes bit-identical to ``transformer._paged_writeback`` (same index
    math: physical page via the block table, offset ``pos+j`` mod page).
    Inactive rows redirect to trash page 0, like every other writer."""
    B, H, W, hd = rows.shape
    page = pool.shape[2]
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)       # (B, W)
    phys = jnp.take_along_axis(block_tables, wpos // page, axis=1)
    if active is not None:
        phys = jnp.where(active[:, None], phys, 0)
    pf = phys.reshape(-1)
    of = (wpos % page).reshape(-1)
    vals = rows.transpose(0, 2, 1, 3).reshape(B * W, H, hd)
    return pool.at[pf, :, of].set(vals.astype(pool.dtype))


def _pool_write_rows_quant(pool, scales, rows, block_tables, pos, active):
    """Quantizing twin of :func:`_pool_write_rows`: the same index math,
    but each (H, hd) row goes through :func:`quantize_kv` first and its
    per-head scale lands in the ``(N, H, page)`` scale pool at the same
    (physical page, offset). Bit-identical bytes to the fused kernel's
    in-launch quantized scatter and to ``_paged_writeback``'s quant
    branch — same helper, same order of operations."""
    B, H, W, hd = rows.shape
    page = pool.shape[2]
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)       # (B, W)
    phys = jnp.take_along_axis(block_tables, wpos // page, axis=1)
    if active is not None:
        phys = jnp.where(active[:, None], phys, 0)
    pf = phys.reshape(-1)
    of = (wpos % page).reshape(-1)
    vals = rows.transpose(0, 2, 1, 3).reshape(B * W, H, hd)
    q, sc = quantize_kv(vals, pool.dtype)
    return (pool.at[pf, :, of].set(q),
            scales.at[pf, :, of].set(sc.astype(scales.dtype)))


def _pad_window(t, Wp):
    W = t.shape[2]
    if W == Wp:
        return t
    return jnp.pad(t, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    k_scale=None, v_scale=None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    mesh=None, slot_axis: Optional[str] = None,
                    head_axis: Optional[str] = None):
    """Read-only paged attention: queries ``q`` (B, H, W, hd) attend the
    first ``lengths[b]`` cached keys of row ``b``, read in place from
    the ``(N, H, page, hd)`` page pools through ``block_tables`` (B, P).
    A row with ``lengths[b] == 0`` yields zeros (the flash convention
    for fully-masked rows). Returns (B, H, W, hd) in ``q.dtype``.

    With ``k_scale``/``v_scale`` (the pool's ``(N, H, page)`` scale
    arrays) the pools hold QUANTIZED values: the scale blocks ride the
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
    Wp = _round_up(W, sublane_multiple(q.dtype))
    qp = _pad_window(q, Wp)
    bt = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    quant = k_scale is not None
    if mesh is None:
        if quant:
            out = _pa_read_call_q(qp, k_pages, v_pages, k_scale, v_scale,
                                  bt, lens, scale=scale,
                                  interpret=bool(interpret))
        else:
            out = _pa_read_call(qp, k_pages, v_pages, bt, lens,
                                scale=scale, interpret=bool(interpret))
        return out[:, :, :W]
    _check_mount(mesh, B, H, slot_axis, head_axis)
    from ..parallel.mesh import get_shard_map
    shard_map, unchecked = get_shard_map()
    row, pool, bt_spec, vec = _mount_specs(slot_axis, head_axis)
    if quant:
        spool = _scale_mount_spec(head_axis)

        def _shard_q(q_, kp_, vp_, ks_, vs_, bt_, len_):
            return _pa_read_call_q(q_, kp_, vp_, ks_, vs_, bt_, len_,
                                   scale=scale, interpret=bool(interpret))

        out = shard_map(_shard_q, mesh=mesh,
                        in_specs=(row, pool, pool, spool, spool,
                                  bt_spec, vec),
                        out_specs=row, **unchecked)(
            qp, k_pages, v_pages, k_scale, v_scale, bt, lens)
        return out[:, :, :W]

    def _shard(q_, kp_, vp_, bt_, len_):
        return _pa_read_call(q_, kp_, vp_, bt_, len_,
                             scale=scale, interpret=bool(interpret))

    out = shard_map(_shard, mesh=mesh,
                    in_specs=(row, pool, pool, bt_spec, vec),
                    out_specs=row, **unchecked)(
        qp, k_pages, v_pages, bt, lens)
    return out[:, :, :W]


def paged_attention_window(q, k_new, v_new, k_pages, v_pages,
                           block_tables, pos, *, active=None,
                           k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           mesh=None, slot_axis: Optional[str] = None,
                           head_axis: Optional[str] = None):
    """Fused decode-window attention + page scatter, one launch.

    Row ``b``'s W queries sit at absolute positions
    ``pos[b] .. pos[b]+W-1``; they attend every cached key strictly
    below ``pos[b]`` (read in place from the pools) plus the window's
    own keys ``k_new``/``v_new`` (B, H, W, hd) under the in-window
    causal mask, and the fresh K/V rows are scattered into their pages
    in the same launch. Rows where ``active`` is False neither write
    their pages (their writes redirect to trash page 0) nor produce
    meaningful context. Returns ``(ctx, k_pages, v_pages)`` with the
    pool buffers updated in place (aliased).

    With ``k_scale``/``v_scale`` (the ``(N, H, page)`` scale pools) the
    page pools hold QUANTIZED values: page reads dequantize in VMEM and
    the in-launch scatter quantizes each fresh row through the
    sanctioned :func:`~mmlspark_tpu.ops.kv_quant.quantize_kv` before
    writing. The return grows to ``(ctx, k_pages, v_pages, k_scale,
    v_scale)`` — scales alias and update in place exactly like pages.

    With ``mesh=`` the attention mounts via ``jax.shard_map`` (heads
    over ``head_axis``, rows optionally over ``slot_axis``) in
    READ-ONLY form, and the fresh rows are scattered by
    :func:`_pool_write_rows` / :func:`_pool_write_rows_quant` outside
    the mount — the written bytes are bit-identical to the fused
    in-kernel scatter, so single-chip and mesh engines produce the same
    pages."""
    if interpret is None:
        interpret = _auto_interpret()
    B, H, W, hd = q.shape
    page = k_pages.shape[2]
    if scale is None:
        scale = float(1.0 / math.sqrt(hd))
    pos = pos.astype(jnp.int32)
    Wp = _round_up(W, sublane_multiple(q.dtype))
    bt = block_tables.astype(jnp.int32)
    quant = k_scale is not None
    if mesh is not None:
        _check_mount(mesh, B, H, slot_axis, head_axis)
        from ..parallel.mesh import get_shard_map
        shard_map, unchecked = get_shard_map()
        row, pool, bt_spec, vec = _mount_specs(slot_axis, head_axis)
        if quant:
            spool = _scale_mount_spec(head_axis)

            def _shard_q(q_, kn_, vn_, kp_, vp_, ks_, vs_, bt_, pos_):
                return _pa_window_read_call_q(
                    q_, kn_, vn_, kp_, vp_, ks_, vs_, bt_, pos_,
                    W=W, scale=scale, interpret=bool(interpret))

            ctx = shard_map(_shard_q, mesh=mesh,
                            in_specs=(row, row, row, pool, pool,
                                      spool, spool, bt_spec, vec),
                            out_specs=row, **unchecked)(
                _pad_window(q, Wp), _pad_window(k_new, Wp),
                _pad_window(v_new, Wp), k_pages, v_pages,
                k_scale, v_scale, bt, pos)
            kp, ks = _pool_write_rows_quant(k_pages, k_scale, k_new,
                                            bt, pos, active)
            vp, vs = _pool_write_rows_quant(v_pages, v_scale, v_new,
                                            bt, pos, active)
            return ctx[:, :, :W], kp, vp, ks, vs

        def _shard(q_, kn_, vn_, kp_, vp_, bt_, pos_):
            return _pa_window_read_call(q_, kn_, vn_, kp_, vp_, bt_, pos_,
                                        W=W, scale=scale,
                                        interpret=bool(interpret))

        ctx = shard_map(_shard, mesh=mesh,
                        in_specs=(row, row, row, pool, pool, bt_spec, vec),
                        out_specs=row, **unchecked)(
            _pad_window(q, Wp), _pad_window(k_new, Wp),
            _pad_window(v_new, Wp), k_pages, v_pages, bt, pos)
        kp = _pool_write_rows(k_pages, k_new, bt, pos, active)
        vp = _pool_write_rows(v_pages, v_new, bt, pos, active)
        return ctx[:, :, :W], kp, vp
    wlo = pos // page
    whi = (pos + W - 1) // page
    if active is not None:
        # an empty write range (lo > hi): the index_map sends every page
        # of the row to trash and the overlay never fires
        wlo = jnp.where(active, wlo, 1)
        whi = jnp.where(active, whi, 0)
    if quant:
        out, kp, vp, ks, vs = _pa_fused_call_q(
            _pad_window(q, Wp), _pad_window(k_new, Wp),
            _pad_window(v_new, Wp), k_pages, v_pages, k_scale, v_scale,
            bt, pos, wlo.astype(jnp.int32), whi.astype(jnp.int32),
            W=W, scale=scale, interpret=bool(interpret))
        return out[:, :, :W], kp, vp, ks, vs
    out, kp, vp = _pa_fused_call(
        _pad_window(q, Wp), _pad_window(k_new, Wp), _pad_window(v_new, Wp),
        k_pages, v_pages, bt, pos,
        wlo.astype(jnp.int32), whi.astype(jnp.int32),
        W=W, scale=scale, interpret=bool(interpret))
    return out[:, :, :W], kp, vp
