"""Delta-rule linear attention with a per-channel decay — the Pallas TPU
decode step (the ``kda`` mixer of ``models/zoo/hybrid.py``).

One token a row: per head, in float32, on a ``(d_k x d_v)`` state,

    S' = diag(alpha) S              (every key channel its own decay)
    u  = v - S'^T k                 (what the state does not yet say of k)
    S  = S' + (beta k) u^T          (the rank-one correction)
    o  = S^T q

which is ``S = (I - beta k k^T) diag(alpha) S + beta k v^T``. Like the
lightning step (``ops/lightning_attention.py``) it is memory bound: it must
read and write every live row's state once. Plain ``jnp`` makes four passes
over the state (the decay, ``S'^T k``, the update's write, the read for
``S^T q``); the kernel makes the two the arithmetic needs and gives the
operation a name a trace can find (``_kda_step_call``).

Grid ``(rows, H / heads-a-step)``; a step holds ``(hb, d, d)`` of state in
VMEM, aliased in and out so the buffer the engine donates is updated in
place. ``q``, ``k``, ``beta k``, ``v`` and ``alpha`` arrive as rows
``(.., 1, d)``; the kernel turns the key-side ones into columns by a
broadcast and a transpose in VMEM, and every product is a broadcast on the
vector unit, exact in float32. A row that is not ``active`` keeps its state.

The chunked form a prefill window runs is plain ``jnp``
(``models.zoo.hybrid.kda_chunk``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _pa

__all__ = ["kda_decode_step"]

F32 = jnp.float32


def _step_kernel(act_ref, q_ref, k_ref, kb_ref, v_ref, a_ref, s_ref,
                 o_ref, so_ref):
    from jax.experimental import pallas as pl

    live = act_ref[pl.program_id(0)] > 0
    hb, hd = s_ref.shape[1], s_ref.shape[2]

    def column(row):        # (1, hd) along the lanes -> [i, j] = row[i]
        return jnp.broadcast_to(row, (hd, hd)).T

    for h in range(hb):                                 # static, 8
        state = s_ref[0, h]                             # (hd, hd)
        decayed = column(a_ref[0, h]) * state
        u = v_ref[0, h] - jnp.sum(column(k_ref[0, h]) * decayed, axis=0,
                                  keepdims=True)
        new = decayed + column(kb_ref[0, h]) * u
        o_ref[0, h] = jnp.sum(column(q_ref[0, h]) * new, axis=0,
                              keepdims=True)
        so_ref[0, h] = jnp.where(live, new, state)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_call(active, q, k, kb, v, alpha, state, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd, _ = state.shape
    hb = 8 if H % 8 == 0 else H
    row = pl.BlockSpec((1, hb, 1, hd), lambda b, h, *_: (b, h, 0, 0))
    mat = pl.BlockSpec((1, hb, hd, hd), lambda b, h, *_: (b, h, 0, 0))
    call = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[row] * 5 + [mat], out_specs=[row, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, hd), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operand indices count the scalar-prefetch argument: the state is
        # operand 6, aliased onto output 1
        input_output_aliases={6: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret)
    return call(active, *(t[:, :, None] for t in (q, k, kb, v, alpha)),
                state)


def kda_decode_step(q, k, v, alpha, beta, state, active, interpret=None):
    """``q``, ``k``, ``v``, ``alpha`` (B, H, d) float32 (``q`` scaled and
    ``k`` normalised by the caller, ``alpha = exp(g)`` the channels'
    decays), ``beta`` (B, H), ``state`` (B, H, d, d) float32, ``active``
    (B,) bool. Returns ``(o (B, H, d), state)``, the state updated in place
    for active rows and untouched for the rest (whose ``o`` is not
    meaningful)."""
    if interpret is None:
        interpret = _pa._auto_interpret()
    k = k.astype(F32)
    o, state = _kda_step_call(
        active.astype(jnp.int32), q.astype(F32), k,
        k * beta.astype(F32)[..., None], v.astype(F32), alpha.astype(F32),
        state, interpret=bool(interpret))
    return o[:, :, 0], state
