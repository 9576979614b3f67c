"""Lightning (linear) attention — the Pallas TPU decode step.

One token a row: per head, in float32, ``S <- exp(-s_h) S + k^T v`` and
``o = q S / sqrt(hd)`` on a ``(hd x hd)`` state. The step is memory bound:
it must read and write every live row's state once (2 x 64 KiB a head at
``hd`` 128) and does two operations a byte. Plain ``jnp`` makes that three
passes (the update, its write, the read for ``q S``); the kernel makes it
the two the arithmetic needs, and gives the operation a name a trace can
find (``_lightning_step_call``).

Grid ``(rows, H / heads-a-step)``; a step holds ``(hb, hd, hd)`` of state in
VMEM, aliased in and out so the buffer the engine donates is updated in
place. ``q``, ``k`` and ``v`` arrive as rows ``(.., 1, hd)`` (a column
``(.., hd, 1)`` would be padded to 128 lanes in HBM, as many bytes as the
state); the kernel turns ``k`` and ``q`` into columns by a broadcast and a
transpose in VMEM, and the outer product and the contraction are broadcasts
on the vector unit, exact in float32. A row that is not ``active`` keeps its state
(a slot mid-prefill must not be touched by the tick).

The chunked form a prefill window runs is plain ``jnp``
(``models.zoo.hybrid.lightning_chunk``): its matrix products are the MXU's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _pa

__all__ = ["lightning_decode_step"]

F32 = jnp.float32


def _step_kernel(act_ref, q_ref, k_ref, v_ref, rate_ref, s_ref,
                 o_ref, so_ref, *, scale):
    from jax.experimental import pallas as pl

    live = act_ref[pl.program_id(0)] > 0
    hb, hd = s_ref.shape[1], s_ref.shape[2]

    def column(row):        # (1, hd) along the lanes -> [i, j] = row[i]
        return jnp.broadcast_to(row, (hd, hd)).T

    for h in range(hb):                                 # static, 8
        state = s_ref[0, h]                             # (hd, hd)
        new = (jnp.exp(-rate_ref[h]) * state
               + column(k_ref[0, h]) * v_ref[0, h])
        o_ref[0, h] = jnp.sum(column(q_ref[0, h]) * new, axis=0,
                              keepdims=True) * scale
        so_ref[0, h] = jnp.where(live, new, state)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lightning_step_call(active, q, k, v, rates, state, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd, _ = state.shape
    hb = 8 if H % 8 == 0 else H
    row = pl.BlockSpec((1, hb, 1, hd), lambda b, h, *_: (b, h, 0, 0))
    mat = pl.BlockSpec((1, hb, hd, hd), lambda b, h, *_: (b, h, 0, 0))
    rate = pl.BlockSpec((hb, 1, 1), lambda b, h, *_: (h, 0, 0))
    call = pl.pallas_call(
        functools.partial(_step_kernel, scale=float(hd) ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[row, row, row, rate, mat], out_specs=[row, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, hd), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operand indices count the scalar-prefetch argument: the state is
        # operand 5, aliased onto output 1
        input_output_aliases={5: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret)
    return call(active, q[:, :, None], k[:, :, None], v[:, :, None],
                rates[:, None, None], state)


def lightning_decode_step(q, k, v, state, active, interpret=None):
    """``q``, ``k``, ``v`` (B, H, hd) float32, ``state`` (B, H, hd, hd)
    float32, ``active`` (B,) bool. Returns ``(o (B, H, hd), state)``, the
    state updated in place for active rows and untouched for the rest
    (whose ``o`` is not meaningful)."""
    from ..models.zoo.hybrid import lightning_rates
    if interpret is None:
        interpret = _pa._auto_interpret()
    o, state = _lightning_step_call(
        active.astype(jnp.int32), q.astype(F32), k.astype(F32),
        v.astype(F32), lightning_rates(q.shape[1]), state,
        interpret=bool(interpret))
    return o[:, :, 0], state
