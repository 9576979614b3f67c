"""Selective state-space decode step (Mamba-2) - the Pallas TPU kernel of the
``ssm`` mixer of ``models/zoo/hybrid.py``.

One token a row: per head ``h`` of group ``g = h // (H / G)``, in float32, on
a state ``S`` of ``head_dim x state`` values (not square),

    S' = a_h S + (d_h u_h) B_g^T        (ONE scalar decay a head; d_h the
    y_h = S' C_g                         token's discretisation step)

with ``a_h = exp(d_h A_h)`` and ``B_g``, ``C_g`` shared by the heads of a
group. Memory bound like the lightning and kda steps: every live row's state
is read and written once; the skip term ``D_h u_h`` is the caller's.

**The state's layout.** A head's ``(P, N)`` state with ``P`` = 64 on the
sublane axis would need ``d u`` as a column a head and the sum over ``N`` as
a lane reduction a head: a transpose in and one out for every eight registers
of state. The pool therefore holds the state TRANSPOSED, TWO HEADS SIDE BY
SIDE on the lane axis: ``(rows, H / 2, N, 2 P)``, entry ``[r, i, n, j * P +
p]`` = ``S[r, 2 i + j][p, n]`` (:func:`pack_state` / :func:`unpack_state`;
the same bytes: 4.19 MB a row at 128 heads of 64 x 128). ``d u`` and ``a``
are then ROWS (the pair's two heads side by side), the sum over ``N`` runs
down the sublanes (vector adds), and the only columns are ``B`` and ``C``,
which a whole group shares: two transposes a GROUP of heads, not two a head.

Grid ``(rows, steps)``; a step holds a block of consecutive pairs in VMEM,
2 MiB of state at most (:func:`pairs_a_step`): whole groups where a group's
pairs fit (8 groups of 8 pairs at 64 KiB a pair: 4 groups a step, 2 steps a
row), else an equal part of ONE group (1 group of 64 pairs: 32 pairs a step,
2 steps a row, both reading the group's one ``B`` and ``C``). The state is
aliased in and out so the buffer the engine donates is updated in place.
Every product is a broadcast on the vector unit, exact in float32. A row
that is not ``active`` keeps its state.

The chunked form a prefill window runs is plain ``jnp``
(``models.zoo.hybrid.ssm_chunk``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _pa

__all__ = ["ssm_decode_step", "pack_state", "unpack_state", "pairs_a_step"]

F32 = jnp.float32
#: the most state a grid step holds (in, and as much again out)
_STEP_BYTES = 2 << 20


def pack_state(state):
    """``(B, H, P, N)`` -> the pool's ``(B, H / 2, N, 2 P)`` (module
    docstring)."""
    B, H, P, N = state.shape
    return state.reshape(B, H // 2, 2, P, N).transpose(0, 1, 4, 2, 3).reshape(
        B, H // 2, N, 2 * P)


def unpack_state(packed):
    """The pool's ``(B, H / 2, N, 2 P)`` -> ``(B, H, P, N)``."""
    B, half, N, P2 = packed.shape
    return packed.reshape(B, half, N, 2, P2 // 2).transpose(
        0, 1, 3, 4, 2).reshape(B, 2 * half, P2 // 2, N)


def _step_kernel(act_ref, a_ref, du_ref, b_ref, c_ref, s_ref, y_ref, so_ref,
                 *, per):
    from jax.experimental import pallas as pl

    live = act_ref[pl.program_id(0)] > 0
    n, lanes = s_ref.shape[2], s_ref.shape[3]

    def column(row):        # (1, n) along the lanes -> [i, j] = row[i]
        return jnp.broadcast_to(row, (lanes, n)).T

    # static loops: the block's groups (or the one group a part of whose
    # pairs the block holds), then ``per`` pairs of each; 32 pairs a step
    # at 64 KiB a pair, however they are grouped
    for g in range(b_ref.shape[1]):
        b_col, c_col = column(b_ref[0, g]), column(c_ref[0, g])
        for i in range(g * per, (g + 1) * per):
            state = s_ref[0, i]                         # (n, lanes)
            new = a_ref[0, i] * state + b_col * du_ref[0, i]
            y_ref[0, i] = jnp.sum(c_col * new, axis=0, keepdims=True)
            so_ref[0, i] = jnp.where(live, new, state)


def pairs_a_step(groups: int, pair_bytes: int, per: int):
    """``(groups, pairs of each)`` a grid step holds, for ``groups`` groups
    of ``per`` pairs: the most whole groups (a divisor of ``groups``) whose
    state fits ``_STEP_BYTES``; where not even one group fits, the largest
    equal part (a divisor of ``per``) of one group that does, a pair at
    least."""
    fit = max(1, _STEP_BYTES // pair_bytes)             # pairs that fit
    if per > fit:
        return 1, max(n for n in range(1, fit + 1) if per % n == 0)
    return max(n for n in range(1, groups + 1)
               if groups % n == 0 and n * per <= fit), per


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_call(active, a, du, b, c, state, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, half, N, lanes = state.shape
    G = b.shape[1]
    gb, pb = pairs_a_step(G, N * lanes * 4, half // G)
    parts = half // G // pb             # grid steps that share one group

    def pairs(r, s, *_):
        return (r, s, 0, 0)

    def group(r, s, *_):
        return (r, s if parts == 1 else s // parts, 0, 0)

    row = pl.BlockSpec((1, gb * pb, 1, lanes), pairs)
    shared = pl.BlockSpec((1, gb, 1, N), group)
    mat = pl.BlockSpec((1, gb * pb, N, lanes), pairs)
    call = pl.pallas_call(
        functools.partial(_step_kernel, per=pb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G // gb * parts),
            in_specs=[row, row, shared, shared, mat], out_specs=[row, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, half, 1, lanes), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operand indices count the scalar-prefetch argument: the state is
        # operand 5, aliased onto output 1
        input_output_aliases={5: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_pa._VMEM_LIMIT_BYTES),
        interpret=interpret)
    return call(active, a[:, :, None], du[:, :, None], b[:, :, None],
                c[:, :, None], state)


def ssm_decode_step(du, a, b, c, state, active, interpret=None):
    """``du`` (B, H, P) float32 the token's input times its step (``d_h
    u_h``), ``a`` (B, H) the heads' decays ``exp(d_h A_h)``, ``b``, ``c``
    (B, G, N) the groups' ``B`` and ``C``, ``state`` the pool's rows (B, H /
    2, N, 2 P) float32 (:func:`pack_state`), ``active`` (B,) bool. Returns
    ``(y (B, H, P) = S' C, state)``, the state updated in place for active
    rows and untouched for the rest (whose ``y`` is not meaningful). Heads
    a group must be even: a pair lies inside one group."""
    if interpret is None:
        interpret = _pa._auto_interpret()
    B, H, P = du.shape
    G = b.shape[1]
    if H % (2 * G):
        raise ValueError(f"{H} heads in {G} groups: a pair of heads must "
                         "lie inside one group")
    y, state = _ssm_step_call(
        active.astype(jnp.int32),
        jnp.repeat(a.astype(F32), P, axis=1).reshape(B, H // 2, 2 * P),
        du.astype(F32).reshape(B, H // 2, 2 * P), b.astype(F32),
        c.astype(F32), state, interpret=bool(interpret))
    return y[:, :, 0].reshape(B, H, P), state
