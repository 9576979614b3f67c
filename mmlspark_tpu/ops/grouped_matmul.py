"""Grouped SwiGLU — the Pallas TPU product over the experts a batch touches.

A routed feed-forward's decode tick multiplies a few dozen rows by a few
dozen of its experts: the work is the experts' bytes, and a dense product
over every expert held reads all of them. Here the rows arrive sorted by
expert and padded to whole tiles of ``TILE`` rows (``parallel.moe``), one
expert a tile; the grid is ONE dimension of tiles under a traced bound (the
tiles in use, as ``ops.paged_attention``'s ragged sweep), and a tile's
weight blocks are picked by its expert's index from a scalar-prefetched
vector, so a step DMAs one expert's ``(D, 2F)`` gate-and-up block and its
``(F, D)`` down block (consecutive tiles of one expert fetch nothing) and an
expert no row reached is never read. A tile computes ``(silu(x W_g) * x W_u)
W_d`` with float32 accumulation; rows past the bound are not written and
hold whatever the buffer held: the caller masks them.

``gated=False`` is the non-gated body ``relu(x W_1)^2 W_2`` (experts that
live in a latent: ``x`` is the latent's rows, ``gate_up`` the ``(E, L, F)``
up projections alone, ``down`` ``(E, F, L)``): the same grid, the same
launch under the same jitted name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _pa

__all__ = ["TILE", "grouped_swiglu"]

#: rows a tile: the bf16 sublane tile, so a tile is one packed vreg row block
TILE = 16
F32 = jnp.float32


def _experts_kernel(expert_ref, x_ref, gu_ref, dn_ref, o_ref):
    x = x_ref[...]                                       # (TILE, D)
    gu = jnp.dot(x, gu_ref[0], preferred_element_type=F32)
    f = gu.shape[1] // 2
    h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    o_ref[...] = jnp.dot(h.astype(x.dtype), dn_ref[0],
                         preferred_element_type=F32)


def _relu2_kernel(expert_ref, x_ref, up_ref, dn_ref, o_ref):
    x = x_ref[...]                                       # (TILE, L)
    h = jnp.maximum(jnp.dot(x, up_ref[0], preferred_element_type=F32), 0.0)
    o_ref[...] = jnp.dot((h * h).astype(x.dtype), dn_ref[0],
                         preferred_element_type=F32)


@functools.partial(jax.jit, static_argnames=("interpret", "gated"))
def _moe_experts_call(tile_expert, total, x, gate_up, down, *, interpret,
                      gated=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = x.shape
    call = pl.pallas_call(
        _experts_kernel if gated else _relu2_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(total,),
            in_specs=[
                pl.BlockSpec((TILE, D), lambda s, e: (s, 0)),
                pl.BlockSpec((1, D, gate_up.shape[2]),
                             lambda s, e: (e[s], 0, 0)),
                pl.BlockSpec((1, down.shape[1], D),
                             lambda s, e: (e[s], 0, 0))],
            out_specs=pl.BlockSpec((TILE, D), lambda s, e: (s, 0))),
        out_shape=jax.ShapeDtypeStruct((R, D), F32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_pa._VMEM_LIMIT_BYTES),
        interpret=interpret)
    return call(tile_expert, x, gate_up, down)


def grouped_swiglu(x, tile_expert, total, gate_up, down, interpret=None,
                   gated=True):
    """``x`` (R, D) rows in tiles of :data:`TILE`, tile ``s`` of them for
    expert ``tile_expert[s]`` (int32, whole up to ``total``, the traced
    number of tiles in use); ``gate_up`` (E, D, 2F) an expert's gate beside
    its up projection, ``down`` (E, F, D). Returns float32 (R, D); rows of
    tiles past ``total`` are NOT written. ``gated=False``: ``gate_up`` is
    the up projection alone, (E, D, F), and the body ``relu(.)^2``."""
    if interpret is None:
        interpret = _pa._auto_interpret()
    return _moe_experts_call(
        tile_expert.astype(jnp.int32), jnp.asarray(total, jnp.int32), x,
        gate_up, down, interpret=bool(interpret), gated=bool(gated))
