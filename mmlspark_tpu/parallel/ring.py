"""Long-context attention: ring attention + Ulysses-style all-to-all.

The reference never shards a sequence (SURVEY.md §5 "Long-context … absent");
its longest-document story is byte-bounded text chunking
(``featurize/text/PageSplitter.scala``). For a TPU framework long context is
a first-class design axis, so the mesh layer ships two sequence-parallel
attention schemes that mount on a ``Mesh`` axis (canonically ``sp``):

* :func:`ring_attention` — K/V blocks rotate around the ring via
  ``lax.ppermute`` while each chip keeps a flash-style streaming softmax
  (running max + normalizer), so no chip ever materializes the full S×S
  score matrix and the sequence scales with the number of chips. Comm rides
  ICI neighbor links — bandwidth-optimal for 1-D rings.
* :func:`ulysses_attention` — ``lax.all_to_all`` reshards (seq → heads)
  before attention and back after, trading one collective for fully local
  attention; better when heads ≫ ring hops.

Both are pure SPMD functions meant to be used inside ``shard_map``; see
``wrap_ring_attention`` for the canonical mounting.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "wrap_ring_attention",
           "local_attention", "attention_transient_bytes",
           "plan_attention_impl"]


def attention_transient_bytes(impl: str, direction: str, B: int, H: int,
                              S: int, sp: int = 1) -> int:
    """Dominant per-chip transient footprint (bytes) of an attention impl.

    The O(S²) score buffers — not the O(S·D) operands — decide whether a
    long-context config compiles at all, so this is the planning number.
    The model is calibrated against the r4/r5 on-chip campaigns, where it
    predicts every success/failure at 4k/16k/64k on a 16 GB v5e:

    * ``full`` fwd keeps ONE live f32 (B, H, S, S) score buffer (XLA fuses
      the softmax into the PV matmul); XLA-autodiff bwd keeps ~3 (saved
      probabilities + dS + the recompute).
    * ``ring`` (dense hops) materializes per-hop (S/sp, S/sp) scores in
      BOTH directions — the custom-VJP forward recompute re-runs the dense
      forward ring (:func:`_ring_vjp_fwd`), while the backward itself is
      blockwise O(S·block).
    * ``ulysses`` is ``full`` with H/sp heads over the full S.
    * ``flash`` / ``ring_flash`` stream: O(S·block) — returned as 0, they
      never hit the quadratic wall.

    ``direction`` is ``"fwd"`` or ``"bwd"``. The head dim does not appear:
    the O(S·D) operand/output buffers are negligible next to the scores at
    every planning-relevant scale.
    """
    if impl in ("flash", "ring_flash"):
        return 0
    bwd_factor = 1 if direction == "fwd" else 3
    if impl == "full":
        return 4 * B * H * S * S * bwd_factor
    if impl == "ring":
        s_local = S // sp
        return 4 * B * H * s_local * s_local  # vjp-fwd recompute dominates
    if impl == "ulysses":
        return 4 * B * max(H // sp, 1) * S * S * bwd_factor
    raise ValueError(f"unknown attention impl {impl!r}")


def plan_attention_impl(impl: str, direction: str, B: int, H: int, S: int,
                        sp: int = 1,
                        hbm_bytes: Optional[float] = None) -> dict:
    """Feasibility verdict for an attention impl on a given chip budget.

    Returns ``{"feasible": bool, "transient_bytes": int, "min_sp": ...}``.
    ``min_sp`` is the smallest sequence-parallel degree at which the impl
    fits (None when no sp helps: ``full`` never shards, and ulysses' bwd
    keeps full-S buffers once H/sp bottoms out). Infeasible configs fail
    at COMPILE time (XLA buffer assignment), with an error that names no
    remedy — callers should consult this planner first and route to flash/ring_flash instead.
    """
    if hbm_bytes is None:
        hbm_bytes = 16e9  # TPU v5e
    need = attention_transient_bytes(impl, direction, B, H, S, sp)
    feasible = need <= hbm_bytes
    min_sp = None
    if not feasible:
        for cand in (2, 4, 8, 16, 32, 64, 128):
            if impl == "ring" and S % cand:
                continue
            if impl == "ulysses" and H % cand:
                continue  # all_to_all splits the head axis exactly
            if attention_transient_bytes(
                    impl, direction, B, H, S, cand) <= hbm_bytes:
                min_sp = cand
                break
    return {"feasible": feasible, "transient_bytes": need, "min_sp": min_sp}


def local_attention(q, k, v, scale: Optional[float] = None):
    """Plain softmax attention, (B, H, S, D) layout, fp32 accumulation."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, preferred_element_type=v.dtype)


def _ring_fwd_impl(q, k, v, axis_name: str, axis_size: int, scale: float,
                   use_flash: bool):
    """The forward ring: returns (o_normalized, L) where L = m + log(l) is
    the per-query GLOBAL logsumexp across every hop's keys — the residual
    the backward pass needs to re-normalize per-hop probabilities."""
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    # accumulators must carry the same "varying over axis_name" type as the
    # rotating K/V blocks for the fori_loop carry to typecheck under shard_map
    o = lax.pcast(jnp.zeros(q.shape, dtype=jnp.float32), (axis_name,), to='varying')
    m = lax.pcast(jnp.full(q.shape[:-1], -jnp.inf, dtype=jnp.float32),
                  (axis_name,), to='varying')
    l = lax.pcast(jnp.zeros(q.shape[:-1], dtype=jnp.float32), (axis_name,), to='varying')

    def hop_flash(o, m, l, k_cur, v_cur):
        from ..ops.flash_attention import flash_attention_with_stats
        o_i, l_i, m_i = flash_attention_with_stats(q, k_cur, v_cur,
                                                   scale=scale)
        m_new = jnp.maximum(m, m_i)
        c_prev = jnp.exp(m - m_new)
        c_i = jnp.exp(m_i - m_new)
        # o_i comes normalized by l_i; un-normalize inside the merge
        o = o * c_prev[..., None] + \
            o_i.astype(jnp.float32) * (l_i * c_i)[..., None]
        l = l * c_prev + l_i * c_i
        return o, m_new, l

    def hop_dense(o, m, l, k_cur, v_cur):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur,
                       preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return o, m_new, l

    hop = hop_flash if use_flash else hop_dense

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        o, m, l = hop(o, m, l, k_cur, v_cur)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_next, v_next

    o, m, l, _, _ = lax.fori_loop(0, axis_size, body, (o, m, l, k, v))
    return (o / l[..., None]).astype(q.dtype), m + jnp.log(l)


def _pick_block(S: int, cap: int = 1024) -> int:
    """Largest divisor of S not above cap (the bwd recompute block size)."""
    b = min(cap, S)
    while S % b:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring(q, k, v, axis_name, axis_size, scale, use_flash):
    return _ring_fwd_impl(q, k, v, axis_name, axis_size, scale, use_flash)[0]


def _ring_vjp_fwd(q, k, v, axis_name, axis_size, scale, use_flash):
    o, L = _ring_fwd_impl(q, k, v, axis_name, axis_size, scale, use_flash)
    return o, (q, k, v, o, L)


def _ring_vjp_bwd(axis_name, axis_size, scale, use_flash, res, do):
    """Ring backward: a SECOND ring pass. Per hop, the per-chip gradient
    contribution is recovered by the flash blockwise-recompute backward with
    the GLOBAL stats substituted (m ← L, l ← 1, so p = exp(s·scale − L) is
    already globally normalized); the dk/dv accumulators TRAVEL WITH their
    K/V blocks, so after ``axis_size`` hops every block arrives home
    carrying the sum of contributions from every query shard. This is the
    ring-attention paper's backward schedule — O(S_local·block) transients,
    never an S×S matrix."""
    from ..ops.flash_attention import _fa_reference_block_bwd

    q, k, v, o, L = res
    B, H, S, D = q.shape
    BH = B * H
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    # fp32 INPUTS to the hop backward: it casts its outputs back to the
    # input dtype, so bf16 inputs would quantize every hop's contribution
    # before the fp32 accumulation — growing error with ring size
    qf = q.reshape(BH, S, D).astype(jnp.float32)
    of = o.reshape(BH, S, D).astype(jnp.float32)
    dof = do.reshape(BH, S, D).astype(jnp.float32)
    Lf = L.reshape(BH, S)
    ones_l = jnp.ones((BH, S), jnp.float32)
    mask = jnp.ones((BH, S), jnp.int32)
    hop_bwd = jax.vmap(functools.partial(
        _fa_reference_block_bwd, causal=False, scale=scale,
        block_k=_pick_block(S)))

    var = lambda t: lax.pcast(t, (axis_name,), to='varying')
    dq0 = var(jnp.zeros((BH, S, D), jnp.float32))
    dk0 = var(jnp.zeros((BH, S, D), jnp.float32))
    dv0 = var(jnp.zeros((BH, S, D), jnp.float32))

    def body(i, carry):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry
        # K/V rotate in their storage dtype (comm bandwidth); cast at use
        dqh, dkh, dvh = hop_bwd(
            qf, k_cur.reshape(BH, S, D).astype(jnp.float32),
            v_cur.reshape(BH, S, D).astype(jnp.float32), mask, of, ones_l,
            Lf, dof)
        dq = dq + dqh.astype(jnp.float32)
        dk_acc = dk_acc + dkh.astype(jnp.float32)
        dv_acc = dv_acc + dvh.astype(jnp.float32)
        # the accumulators rotate WITH the blocks they belong to
        rot = lambda t: lax.ppermute(t, axis_name, perm)
        return dq, rot(dk_acc), rot(dv_acc), rot(k_cur), rot(v_cur)

    dq, dk, dv, _, _ = lax.fori_loop(
        0, axis_size, body, (dq0, dk0, dv0, k, v))
    shape = (B, H, S, D)
    return (dq.reshape(shape).astype(q.dtype),
            dk.reshape(shape).astype(k.dtype),
            dv.reshape(shape).astype(v.dtype))


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, axis_size: int,
                   scale: Optional[float] = None, use_flash: bool = False):
    """SPMD ring attention over a sequence-sharded axis.

    Args are local shards (B, H, S/n, D). Returns the local output shard.
    Streaming-softmax accumulators are fp32; K/V rotate ``axis_size`` hops.

    ``use_flash=True`` computes each hop's local attention with the Pallas
    streaming kernel and merges the per-hop ``(o, l, m)`` stats (log-sum-exp
    merge) — per-chip memory drops from O(S_local²) scores to O(S_local),
    which is the ring-attention paper's actual memory claim.

    Differentiable: a ring-level custom VJP runs a second ring pass whose
    per-hop gradients come from the flash blockwise recompute with global
    (L = m + log l) statistics, with dk/dv accumulators traveling alongside
    their K/V blocks. (Before this VJP, autodiff through the flash-inner
    merge produced silently WRONG gradients — the stats path had no VJP.)
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    return _ring(q, k, v, axis_name, axis_size, float(scale),
                 bool(use_flash))


def ulysses_attention(q, k, v, axis_name: str, axis_size: int,
                      scale: Optional[float] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Local shards are (B, H, S/n, D) with heads replicated; the all-to-all
    swaps to (B, H/n, S, D) — full sequence, a slice of heads — runs plain
    attention locally, and swaps back.
    """
    def scatter_heads(t):
        # (B, H, S/n, D) -> (B, H/n, S, D)
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def gather_heads(t):
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = local_attention(qh, kh, vh, scale)
    return gather_heads(out)


def wrap_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        impl: str = "ring"):
    """Lift the SPMD kernel to global arrays via shard_map.

    Returns ``fn(q, k, v)`` over global (B, H, S, D) arrays sequence-sharded
    on ``axis_name``.
    """
    n = mesh.shape[axis_name]
    if impl not in ("ring", "ring_flash", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    spec = P(None, None, axis_name, None)

    # the vma/replication check must be off for the ring impls: the
    # pallas_call inside ring_flash cannot declare its varying-axes type,
    # and the ring VJP's blockwise-recompute scan initializes its carry
    # unvarying (mesh.py:get_shard_map)
    from .mesh import get_shard_map
    shard_map, unchecked = get_shard_map()
    kwargs = unchecked if impl in ("ring", "ring_flash") else {}

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, **kwargs)
    def fn(q, k, v):
        if impl == "ulysses":
            return ulysses_attention(q, k, v, axis_name=axis_name,
                                     axis_size=n)
        return ring_attention(q, k, v, axis_name=axis_name, axis_size=n,
                              use_flash=(impl == "ring_flash"))

    return fn
