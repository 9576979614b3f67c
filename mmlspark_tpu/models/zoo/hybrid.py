"""Hybrid decoder block — linear-attention state beside sparse-attention pages.

``TransformerConfig.mixers`` names one mixer a layer (MiniCPM-SALA's shape:
lightning linear attention on three layers of four, InfLLM-V2 block-sparse
softmax attention on the fourth). Everything here computes ONE thing, a
window of ``W`` tokens a row continuing that row's cache, and the public
entry points of ``transformer.py`` (``transformer_apply``, ``decode_step``,
``prefill_cache``, ``decode_step_paged``, ``decode_window_paged``) reach it
when ``cfg.mixers`` is set:

* ``lightning`` — per head, in float32, ``S_t = lambda_h S_(t-1) + k_t^T
  v_t``, ``o_t = q_t S_t / sqrt(hd)``, ``lambda_h = exp(-s_h)``,
  ``s_h = 2^(-8h/H)``; q and k RMS-normed per head and rotated (RoPE). The
  cache entry is the state ``(rows, H, hd, hd)`` float32, not pages. A
  window runs the chunked form (decays taken from position differences,
  never as a ratio of powers); the decode tick runs the Pallas step of
  ``ops/lightning_attention.py``.
* ``sparse`` — grouped-query softmax attention without positions over a
  paged K/V cache plus a cache of compressed keys (the mean of
  ``kernel_size`` keys every ``kernel_stride``, entry ``f`` for the window
  that ENDS at position ``stride * f + stride - 1``), a row a slot like a
  state: the block scorer reads all of a row's entries every tick, and
  gathered through a block table that cost more than the attention did
  (PERF.md, PR 29). A query with more than
  ``dense_len`` of context attends the blocks ``sparse_select`` chooses for
  its KV group; the decode tick hands those blocks to
  ``ops.paged_attention.paged_attention_selected``, a window masks them.

Both mixers end in a sigmoid output gate; the block is bias-free with
RMSNorm and SwiGLU, and carries muP's three scalings.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .transformer import (TransformerConfig, _embed, _rms, _rope_tables,
                          _rot_half, head)

__all__ = ["check_config", "dims", "init_hybrid", "init_hybrid_cache",
           "init_hybrid_pool", "lightning_rates", "lightning_chunk",
           "sparse_select", "head", "window_contiguous", "window_paged",
           "SLOT_KEYS"]

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_NEG = -1e30
#: keys of a pool layer dict whose axis 0 is the SLOT, not the physical page:
#: a lightning layer's state and a sparse layer's compressed keys. A cached
#: prefix keeps a snapshot of these rows beside its pages.
SLOT_KEYS = ("state", "ck")
#: keys of K/V a masked window folds at a time (a 32k context in one piece
#: would hold a gigabyte of scores)
_KEY_TILE = 2048


def dims(cfg: TransformerConfig):
    """(query heads, KV heads of the sparse layers, head size)."""
    return (cfg.heads, cfg.kv_heads or cfg.heads,
            cfg.head_dim or cfg.d_model // cfg.heads)


def check_config(cfg: TransformerConfig) -> None:
    if len(cfg.mixers) != cfg.layers:
        raise ValueError(f"{len(cfg.mixers)} mixers for {cfg.layers} layers")
    unknown = set(cfg.mixers) - {"lightning", "sparse"}
    if unknown:
        raise ValueError(f"unknown mixer kinds {sorted(unknown)} "
                         "(lightning | sparse)")
    if not cfg.causal or cfg.moe_experts or cfg.use_flash:
        raise ValueError("a hybrid decoder is causal, dense in its "
                         "feed-forward and does not take use_flash")
    H, Hkv, hd = dims(cfg)
    if H % Hkv or hd % 2:
        raise ValueError(f"heads {H} / kv_heads {Hkv} / head_dim {hd}")
    if "sparse" in cfg.mixers:
        sp = cfg.sparse
        if sp is None:
            raise ValueError("sparse layers need cfg.sparse")
        if (sp.kernel_size % sp.kernel_stride
                or sp.block_size % sp.kernel_stride):
            raise ValueError("kernel_size and block_size must be multiples "
                             "of kernel_stride")
        forced = sp.init_blocks + sp.window_size // sp.block_size + 1
        if forced > sp.topk:
            raise ValueError(f"first blocks and window force {forced} "
                             f"blocks, more than topk {sp.topk}")


def init_hybrid(cfg: TransformerConfig, seed: int = 0) -> Dict:
    """Random parameters in the pytree the hybrid block reads."""
    check_config(cfg)
    rng = np.random.default_rng(seed)
    H, Hkv, hd = dims(cfg)
    D = cfg.d_model

    def dense(din, dout, scale=None):
        s = scale or np.sqrt(2.0 / (din + dout))
        return {"w": rng.normal(0, s, (din, dout)).astype(np.float32)}

    def ones(n):
        return {"scale": np.ones(n, np.float32)}

    layers = []
    for kind in cfg.mixers:
        kv = H if kind == "lightning" else Hkv
        lp = {"ln1": ones(D), "ln2": ones(D),
              "q": dense(D, H * hd), "k": dense(D, kv * hd),
              "v": dense(D, kv * hd), "g": dense(D, H * hd),
              "o": dense(H * hd, D),
              "q_norm": ones(hd), "k_norm": ones(hd),
              "gate": dense(D, cfg.d_ff), "up": dense(D, cfg.d_ff),
              "down": dense(cfg.d_ff, D)}
        if kind == "lightning":
            lp["o_norm"] = ones(H * hd)
        layers.append(lp)
    return {"embed": {"tok": dense(cfg.vocab, D, 0.02)["w"]},
            "layers": layers, "final_ln": ones(D),
            "lm_head": dense(D, cfg.vocab, 0.02)}


# ---- caches -----------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def init_hybrid_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Contiguous per-layer cache: ``{"state"}`` (B, H, hd, hd) float32 for
    a lightning layer; ``{"k", "v"}`` (B, Hkv, L, hd) and the compressed
    keys ``{"ck"}`` (B, Hkv, L / stride, hd) for a sparse one, ``L`` being
    ``max_len`` rounded up to whole blocks."""
    H, Hkv, hd = dims(cfg)
    out = []
    for kind in cfg.mixers:
        if kind == "lightning":
            out.append({"state": jnp.zeros((batch, H, hd, hd), F32)})
        else:
            sp = cfg.sparse
            L = _round_up(max_len, sp.block_size)
            kv = jnp.zeros((batch, Hkv, L, hd), cfg.dtype)
            out.append({"k": kv, "v": kv, "ck": jnp.zeros(
                (batch, Hkv, L // sp.kernel_stride, hd), cfg.dtype)})
    return out


def pool_shapes(cfg: TransformerConfig, num_pages: int, page_size: int,
                slots: int, positions: int):
    """Per layer ``{key: (shape, dtype)}`` of the engine's cache: pages
    (K beside V, as every pool) and a row of compressed keys a slot (for
    ``positions`` positions) for a sparse layer, one state row a slot for a
    lightning layer."""
    H, Hkv, hd = dims(cfg)
    out = []
    for kind in cfg.mixers:
        if kind == "lightning":
            out.append({"state": ((slots, H, hd, hd), F32)})
        else:
            s = cfg.sparse.kernel_stride
            if cfg.sparse.block_size % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide the sparse block "
                    f"size {cfg.sparse.block_size}")
            out.append({
                "kv": ((num_pages, Hkv, page_size, 2 * hd), cfg.dtype),
                "ck": ((slots, Hkv, -(-positions // s), hd), cfg.dtype)})
    return out


def init_hybrid_pool(cfg, num_pages: int, page_size: int, slots: int,
                     positions: int):
    return [{k: jnp.zeros(*sd) for k, sd in layer.items()}
            for layer in pool_shapes(cfg, num_pages, page_size, slots,
                                     positions)]


# ---- shared pieces ----------------------------------------------------------

def _proj(x, p, dt):
    return x @ p["w"].astype(dt)


def _heads(t, n, hd):
    B, W, _ = t.shape
    return t.reshape(B, W, n, hd).transpose(0, 2, 1, 3)


def _head_rms(t, p, eps=1e-6):
    t = t.astype(F32)
    return (t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
            * p["scale"])


def _gated_out(lp, x, o, cfg, norm: bool):
    """``W_o(sigmoid(W_g x) * o)``, ``o`` (B, H, W, hd) float32, RMS-normed
    over all heads first on a lightning layer."""
    dt = cfg.dtype
    B, H, W, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(B, W, H * hd)
    if norm:
        o = _rms(o, lp["o_norm"])
    gate = jax.nn.sigmoid(_proj(x, lp["g"], dt).astype(F32))
    return (gate * o).astype(dt) @ lp["o"]["w"].astype(dt)


def _swiglu(lp, x, dt):
    y = jax.nn.silu(_proj(x, lp["gate"], dt)) * _proj(x, lp["up"], dt)
    return _proj(y, lp["down"], dt)


# ---- lightning --------------------------------------------------------------

def lightning_rates(H: int):
    """``s_h = 2^(-8h/H)``, h = 1..H: head h decays by ``exp(-s_h)`` a
    position (the Lightning Attention-2 convention)."""
    return 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H)


def _lightning_qkv(lp, x, wpos, cfg):
    H, _, hd = dims(cfg)
    dt = cfg.dtype
    q = _head_rms(_heads(_proj(x, lp["q"], dt), H, hd), lp["q_norm"])
    k = _head_rms(_heads(_proj(x, lp["k"], dt), H, hd), lp["k_norm"])
    v = _heads(_proj(x, lp["v"], dt), H, hd).astype(F32)
    cos, sin = _rope_tables(wpos, hd, cfg.rope_theta, F32)   # (B, W, hd/2)
    cos, sin = cos[:, None], sin[:, None]
    return _rot_half(q, cos, sin), _rot_half(k, cos, sin), v


def lightning_chunk(q, k, v, state, n_valid):
    """The chunked form over one window: ``q``, ``k``, ``v`` (B, H, W, hd)
    float32, ``state`` (B, H, hd, hd) the state before the window,
    ``n_valid`` (B,) the real lanes of each row (the rest is padding and
    neither attends nor reaches the state; 0 leaves a row's state as it
    was). Returns ``(o (B, H, W, hd), state after lane n_valid - 1)``.
    Every decay is ``exp(-s_h * (a difference of positions))``."""
    B, H, W, hd = q.shape
    s = lightning_rates(H)
    j = jnp.arange(W)
    valid = j[None] < n_valid[:, None]                          # (B, W)
    k = jnp.where(valid[:, None, :, None], k, 0.0)
    diff = (j[:, None] - j[None, :]).astype(F32)
    decay = jnp.where(diff >= 0,
                      jnp.exp(-s[:, None, None] * jnp.maximum(diff, 0.0)),
                      0.0)                                      # (H, W, W)
    scores = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HI) * decay
    o = jnp.einsum("bhts,bhsd->bhtd", scores, v, precision=HI)
    carried = jnp.exp(-s[:, None] * (j + 1.0))                  # (H, W)
    o = o + carried[None, :, :, None] * jnp.einsum(
        "bhtd,bhde->bhte", q, state, precision=HI)
    n = n_valid.astype(F32)[:, None, None]                      # (B, 1, 1)
    left = jnp.where(valid[:, None], jnp.exp(
        -s[None, :, None] * jnp.maximum(n - 1.0 - j, 0.0)), 0.0)  # (B, H, W)
    new = (jnp.exp(-s[None, :, None] * n)[..., None] * state
           + jnp.einsum("bhsd,bhse->bhde", k * left[..., None], v,
                        precision=HI))
    return o * hd ** -0.5, new


# ---- sparse -----------------------------------------------------------------

def _sparse_qkv(lp, x, cfg):
    H, Hkv, hd = dims(cfg)
    dt = cfg.dtype
    q = _head_rms(_heads(_proj(x, lp["q"], dt), H, hd), lp["q_norm"])
    k = _head_rms(_heads(_proj(x, lp["k"], dt), Hkv, hd), lp["k_norm"])
    v = _heads(_proj(x, lp["v"], dt), Hkv, hd)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _ck_windows(pos, n_valid, W, sp):
    """End positions ``e`` (B, n) of the compressed-key windows that can
    complete while ``pos .. pos + n_valid - 1`` are written, and which of
    them do. Window ``f`` ends at ``stride * f + stride - 1``."""
    s = sp.kernel_stride
    n = W // s + 1
    e0 = jnp.maximum((pos + s) // s * s - 1, sp.kernel_size - 1)
    e = e0[:, None] + s * jnp.arange(n, dtype=jnp.int32)[None]
    ok = e < (pos + n_valid)[:, None]
    return e, ok


def sparse_select(q, ck, t, sp):
    """The blocks each query's KV group attends once its context passes
    ``dense_len``. ``q`` (B, Hq, W, hd), ``ck`` (B, Hkv, F, hd) compressed
    keys (entry ``f`` = the window ending at ``stride * f + stride - 1``),
    ``t`` (B, W) the queries' positions. Returns ``(idx, ok)``, both
    (B, Hkv, W, K), K = min(topk, blocks): logical block ids and whether
    each entry is a block at all (a short context has fewer than K)."""
    B, Hq, W, hd = q.shape
    G, Fn = ck.shape[1], ck.shape[2]
    s, ks, bs = sp.kernel_stride, sp.kernel_size, sp.block_size
    r = bs // s
    nb = -(-Fn // r)
    qg = q.reshape(B, G, Hq // G, W, hd)
    logits = jnp.einsum("bghwd,bgfd->bghwf", qg, ck,
                        preferred_element_type=F32) * hd ** -0.5
    ends = s * jnp.arange(Fn) + s - 1
    fvalid = ((ends[None, None] <= t[..., None])
              & (ends >= ks - 1))[:, None, None]               # (B,1,1,W,F)
    p = jax.nn.softmax(jnp.where(fvalid, logits, _NEG), axis=-1)
    p = jnp.where(fvalid, p, 0.0).sum(axis=2)                   # (B, G, W, F)
    # a block's score: the best window that overlaps it, windows
    # r*b .. r*b + r + ks/s - 2
    extra = ks // s - 1
    p = jnp.pad(p, ((0, 0),) * 3 + ((0, nb * r + extra - Fn),))
    score = jax.lax.reduce_window(
        p, -jnp.inf, jax.lax.max, (1, 1, 1, r + extra), (1, 1, 1, r),
        "VALID")                                                # (B, G, W, nb)
    b = jnp.arange(nb)
    first = jnp.maximum(t - sp.window_size + 1, 0) // bs
    forced = (b < sp.init_blocks) | (b >= first[..., None])     # (B, W, nb)
    live = b <= (t // bs)[..., None]
    score = jnp.where(forced[:, None], 1e9, score)
    score = jnp.where(live[:, None], score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(sp.topk, nb))
    return idx.astype(jnp.int32), vals > -jnp.inf


def _allowed_keys(idx, ok, t, sp, L):
    """(B, G, W, L) bool: the keys each query may attend — causal, and past
    ``dense_len`` of context only inside its selected blocks."""
    bs = sp.block_size
    nb = -(-L // bs)
    sel = jnp.put_along_axis(
        jnp.zeros(idx.shape[:-1] + (max(nb, idx.shape[-1]),), bool),
        idx, ok, axis=-1, inplace=False)
    sel = jnp.repeat(sel, bs, axis=-1)[..., :L]
    dense = (t + 1 <= sp.dense_len)[:, None, :, None]
    causal = (jnp.arange(L)[None, None] <= t[..., None])[:, None]
    return causal & (dense | sel)


def _masked_attention(q, k, v, allowed, t_max):
    """Softmax attention of ``q`` (B, Hq, W, hd) over ``k``/``v``
    (B, Hkv, L, hd) under ``allowed`` (B, Hkv, W, L), grouped-query, folded
    a tile of keys at a time up to position ``t_max``. float32 out."""
    B, Hq, W, hd = q.shape
    G, L = k.shape[1], k.shape[2]
    qg = q.reshape(B, G, Hq // G, W, hd)
    scale = hd ** -0.5
    T = min(L, _KEY_TILE)

    def fold(carry, ks_, vs_, al):
        m, l, acc = carry
        s = jnp.einsum("bghwd,bgud->bghwu", qg, ks_,
                       preferred_element_type=F32) * scale
        al = al[:, :, None]
        s = jnp.where(al, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * al
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bghwu,bgud->bghwd", p.astype(vs_.dtype), vs_,
            preferred_element_type=F32)
        return m_new, l, acc

    shape = (B, G, Hq // G, W)
    init = (jnp.full(shape, _NEG, F32), jnp.zeros(shape, F32),
            jnp.zeros(shape + (hd,), F32))
    if L == T:
        _, l, acc = fold(init, k, v, allowed)
    else:
        short = -L % T                      # whole tiles (none at 32k)
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, short), (0, 0)))
                for a in (k, v))
        allowed = jnp.pad(allowed, ((0, 0),) * 3 + ((0, short),))

        def body(i, carry):
            def tile(a, axis):
                return jax.lax.dynamic_slice_in_dim(a, i * T, T, axis=axis)
            return fold(carry, tile(k, 2), tile(v, 2), tile(allowed, 3))
        _, l, acc = jax.lax.fori_loop(0, t_max // T + 1, body, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(B, Hq, W, hd)


def _sparse_contiguous(lp, x, wpos, pos, n_valid, c, cfg):
    """A sparse layer over a contiguous cache: write the window's K/V and
    the compressed keys it completes, select, attend under the mask."""
    sp = cfg.sparse
    s, ks = sp.kernel_stride, sp.kernel_size
    W = x.shape[1]
    q, k, v = _sparse_qkv(lp, x, cfg)
    L = c["k"].shape[2]
    lane_ok = jnp.arange(W)[None] < n_valid[:, None]
    dest = jnp.where(lane_ok, wpos, L)          # padding lanes are dropped

    def put(buf, val, idx):                     # (Hkv, L, hd) <- (Hkv, W, hd)
        return buf.at[:, idx].set(val, mode="drop")

    kc = jax.vmap(put)(c["k"], k, dest)
    vc = jax.vmap(put)(c["v"], v, dest)
    e, ok = _ck_windows(pos, n_valid, W, sp)
    src = jnp.clip(e[..., None] - (ks - 1) + jnp.arange(ks), 0, L - 1)
    rows = jax.vmap(lambda kb, ib: kb[:, ib])(kc, src)   # (B,Hkv,n,ks,hd)
    means = rows.astype(F32).mean(axis=3).astype(cfg.dtype)
    Fn = c["ck"].shape[2]
    f = jnp.where(ok, (e + 1) // s - 1, Fn)
    ck = jax.vmap(put)(c["ck"], means, f)
    idx, sel_ok = sparse_select(q, ck, wpos, sp)
    allowed = _allowed_keys(idx, sel_ok, wpos, sp, L)
    o = _masked_attention(q, kc, vc, allowed, jnp.max(wpos))
    return _gated_out(lp, x, o, cfg, norm=False), {"k": kc, "v": vc,
                                                   "ck": ck}


def _sparse_paged(lp, x, wpos, pos, n_valid, c, bt, cfg, page, kernel,
                  slot):
    """A sparse layer over the page pool. K/V writes go through the block
    table (padding lanes and idle rows to trash page 0); the compressed keys
    are the rows' own (row ``slot`` for a one-row prefill window). The
    decode tick (``kernel``: one query a row) hands the selected blocks to
    the Pallas kernel, which reads them in place; a window gathers its
    row's pages and masks."""
    from ...ops.paged_attention import pack_kv, split_kv
    sp = cfg.sparse
    s, ks = sp.kernel_stride, sp.kernel_size
    B, W, _ = x.shape
    H, Hkv, hd = dims(cfg)
    P = bt.shape[1]
    q, k, v = _sparse_qkv(lp, x, cfg)
    lane_ok = jnp.arange(W)[None] < n_valid[:, None]

    def phys(positions, okay):
        pg = jnp.take_along_axis(bt, jnp.clip(positions // page, 0, P - 1),
                                 axis=1)
        return jnp.where(okay, pg, 0)

    # every index names (page, head, offset) and the window is the minor
    # axis alone: a scatter over the page and offset axes with the heads
    # sliced makes the chip lay the whole pool out anew around it
    heads_ = jnp.arange(Hkv)[None]
    rows = pack_kv(k, v).transpose(0, 2, 1, 3).reshape(B * W, Hkv, 2 * hd)
    kv = c["kv"].at[phys(wpos, lane_ok).reshape(-1, 1), heads_,
                    (wpos % page).reshape(-1, 1)].set(rows)
    # the compressed keys this write completes. Their keys lie in the few
    # pages over [pos - ks + 1, pos + W + s): whole pages gathered, one
    # slice a row, sums by stride (a gather row by row is a sequential loop
    # on the chip, 2.4 us a row: 2.5 ms of an 11.9 ms tick, PERF.md PR 29)
    e, ok = _ck_windows(pos, n_valid, W, sp)
    n, m = e.shape[1], ks // s
    n_pg = -(-(page + W + ks + s) // page)
    first = jnp.maximum(pos - ks + 1, 0) // page
    near = kv[phys((first[:, None] + jnp.arange(n_pg)) * page, True)]
    near = near[..., :hd].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, n_pg * page, hd)
    start = e[:, 0] - (ks - 1) - first * page
    seg = jax.vmap(lambda a, r: jax.lax.dynamic_slice_in_dim(
        a, r, (n - 1 + m) * s, axis=1))(near, start)
    strides = seg.astype(F32).reshape(B, Hkv, n - 1 + m, s, hd).sum(axis=3)
    means = sum(strides[:, :, i:i + n] for i in range(m)) / ks
    ck = (c["ck"] if slot is None else
          jax.lax.dynamic_slice_in_dim(c["ck"], slot, 1, axis=0))
    f = jnp.where(ok, (e + 1) // s - 1, ck.shape[2])    # dropped: no window
    ck = ck.at[jnp.arange(B)[:, None, None], heads_[..., None],
               f[:, None, :]].set(means.astype(cfg.dtype), mode="drop")
    idx, sel_ok = sparse_select(q, ck, wpos, sp)
    if kernel:
        o = _selected_decode(q, kv, bt, pos, n_valid, idx, sel_ok, cfg, page)
    else:
        L = P * page
        kc, vc = split_kv(kv[bt].transpose(0, 2, 1, 3, 4).reshape(
            B, Hkv, L, 2 * hd))
        allowed = _allowed_keys(idx, sel_ok, wpos, sp, L)
        o = _masked_attention(q, kc, vc, allowed, jnp.max(wpos))
    if slot is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(c["ck"], ck, slot, axis=0)
    return _gated_out(lp, x, o, cfg, norm=False), {"kv": kv, "ck": ck}


def _selected_decode(q, kv, bt, pos, n_valid, idx, sel_ok, cfg, page):
    """One query a row over the blocks chosen for each (row, KV group), read
    in place. A row still under ``dense_len`` lists every block up to its
    own; the kernel walks ``topk`` blocks unless such a row has more."""
    from ...ops.paged_attention import paged_attention_selected
    sp = cfg.sparse
    B, Hq, _, hd = q.shape
    G = idx.shape[1]
    K = idx.shape[-1]
    pp = sp.block_size // page                  # pages a block
    n_dense = min(-(-sp.dense_len // sp.block_size),
                  -(-bt.shape[1] // pp))
    cur = pos // sp.block_size
    dense_row = (pos + 1 <= sp.dense_len) & (n_valid > 0)

    def pages_of(blocks, okay):                 # (B, G, n) -> (B, G, n*pp)
        pages = blocks[..., None] * pp + jnp.arange(pp)
        return jnp.where(okay[..., None], pages, -1).reshape(B, G, -1)

    def walk(n):
        every = jnp.broadcast_to(jnp.arange(n)[None, None], (B, G, n))
        chosen = jnp.pad(idx[:, :, 0, :n], ((0, 0), (0, 0),
                                             (0, max(0, n - K))))
        chosen_ok = jnp.pad(sel_ok[:, :, 0, :n], ((0, 0), (0, 0),
                                                  (0, max(0, n - K))))
        d = dense_row[:, None, None]
        blocks = jnp.where(d, every, chosen)
        okay = jnp.where(d, every <= cur[:, None, None], chosen_ok)
        okay = okay & (n_valid > 0)[:, None, None]
        out = paged_attention_selected(
            q[:, :, 0].reshape(B, G, Hq // G, hd), kv, bt,
            pages_of(blocks, okay), pos + 1)
        return out.reshape(B, Hq, 1, hd).astype(F32)

    if n_dense <= K:
        return walk(K)
    wide = jnp.any(dense_row & (cur >= K))
    return jax.lax.cond(wide, lambda: walk(n_dense), lambda: walk(K))


# ---- the window -------------------------------------------------------------

def _finish(params, h, cfg, n_valid, last_only):
    """Final norm with muP's logit scaling folded in: hidden states of every
    lane, or with ``last_only`` of lane ``n_valid - 1`` alone."""
    hidden = (_rms(h.astype(F32), params["final_ln"])
              * cfg.logit_scale).astype(cfg.dtype)
    if last_only:
        last = jnp.maximum(n_valid - 1, 0)[:, None, None]
        hidden = jnp.take_along_axis(hidden, last, axis=1)[:, 0]
    return hidden


def _window(params, tokens, pos, cfg, n_valid, mixer, last_only):
    """The layer loop shared by both cache forms; ``mixer(kind, lp, x, wpos,
    layer index)`` returns the mixer's output and records its new cache."""
    dt = cfg.dtype
    W = tokens.shape[1]
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)
    h = _embed(params, tokens, cfg) * jnp.asarray(cfg.embed_scale, dt)
    rs = jnp.asarray(cfg.residual_scale, dt)
    for i, (kind, lp) in enumerate(zip(cfg.mixers, params["layers"])):
        x = _rms(h.astype(F32), lp["ln1"]).astype(dt)
        h = h + rs * mixer(i, kind, lp, x, wpos).astype(dt)
        x = _rms(h.astype(F32), lp["ln2"]).astype(dt)
        h = h + rs * _swiglu(lp, x, dt)
    return _finish(params, h, cfg, n_valid, last_only)


def _lanes(tokens, pos, n_valid, active):
    B, W = tokens.shape
    pos = pos.astype(jnp.int32)
    n_valid = (jnp.full((B,), W, jnp.int32) if n_valid is None
               else n_valid.astype(jnp.int32))
    if active is not None:
        n_valid = jnp.where(active, n_valid, 0)
    return pos, n_valid


def _fresh(state, pos, n_valid):
    """A row whose window starts at position 0 starts from a zero state:
    what resets a reused slot."""
    new = ((pos == 0) & (n_valid > 0))[:, None, None, None]
    return jnp.where(new, 0.0, state)


def window_contiguous(params: Dict, tokens, pos, cache, cfg, *,
                      n_valid=None, active=None, last_only=False):
    """``W`` tokens a row at positions ``pos[b] ..`` continuing a contiguous
    cache (:func:`init_hybrid_cache`): the full forward (``pos`` 0 over an
    empty cache), ``prefill_cache``, ``decode_step``. Returns ``(final
    hidden states, new cache)``; :func:`head` makes logits of them."""
    check_config(cfg)
    pos, n_valid = _lanes(tokens, pos, n_valid, active)
    new_cache = [None] * cfg.layers

    def mixer(i, kind, lp, x, wpos):
        c = cache[i]
        if kind == "lightning":
            q, k, v = _lightning_qkv(lp, x, wpos, cfg)
            o, st = lightning_chunk(q, k, v, _fresh(c["state"], pos, n_valid),
                                    n_valid)
            new_cache[i] = {"state": st}
            return _gated_out(lp, x, o, cfg, norm=True)
        y, new_cache[i] = _sparse_contiguous(lp, x, wpos, pos, n_valid, c,
                                             cfg)
        return y

    hidden = _window(params, tokens, pos, cfg, n_valid, mixer, last_only)
    return hidden, new_cache


def window_paged(params: Dict, tokens, pos, bufs, block_tables, cfg, *,
                 page_size: int, impl: str = "kernel", n_valid=None,
                 active=None, slot=None, last_only=False):
    """The engine's window over its pool (:func:`pool_shapes`): pages
    through ``block_tables`` for the sparse layers' K/V; their compressed
    keys and the lightning layers' states are rows a slot — row ``b`` of
    the batch is slot ``b`` (the decode tick, every slot a row), or with
    ``slot`` the one row of a prefill chunk is that slot's. ``impl="kernel"`` runs the two Pallas
    decode kernels when the window is one token; a longer window, and
    ``impl="gather"`` always, gather and mask. Returns ``(logits, bufs)``."""
    from ...ops.lightning_attention import lightning_decode_step
    check_config(cfg)
    pos, n_valid = _lanes(tokens, pos, n_valid, active)
    kernel = impl == "kernel" and tokens.shape[1] == 1
    new_bufs = [None] * cfg.layers

    def mixer(i, kind, lp, x, wpos):
        c = bufs[i]
        if kind == "sparse":
            y, new_bufs[i] = _sparse_paged(lp, x, wpos, pos, n_valid, c,
                                           block_tables, cfg, page_size,
                                           kernel, slot)
            return y
        q, k, v = _lightning_qkv(lp, x, wpos, cfg)
        rows = (c["state"] if slot is None else
                jax.lax.dynamic_slice_in_dim(c["state"], slot, 1, axis=0))
        if kernel:
            # a decoding row is never at position 0 (a prompt has a token),
            # so the tick needs no reset and no pass over the states for one
            o, st = lightning_decode_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                          rows, n_valid > 0)
            o = o[:, :, None]
        else:
            o, st = lightning_chunk(q, k, v, _fresh(rows, pos, n_valid),
                                    n_valid)
        new_bufs[i] = {"state": st if slot is None else
                       jax.lax.dynamic_update_slice_in_dim(
                           c["state"], st, slot, axis=0)}
        return _gated_out(lp, x, o, cfg, norm=True)

    hidden = _window(params, tokens, pos, cfg, n_valid, mixer, last_only)
    return head(params, hidden), new_bufs
