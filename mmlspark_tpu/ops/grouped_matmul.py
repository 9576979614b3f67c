"""Grouped SwiGLU — the Pallas TPU product over the experts a batch touches.

A routed feed-forward's decode tick multiplies a few dozen rows by a few
dozen of its experts: the work is the experts' bytes, and a dense product
over every expert held reads all of them. Here the rows arrive sorted by
expert and padded to whole tiles of ``TILE`` rows (``parallel.moe``), one
expert a tile; the grid is ONE dimension of RUNS under a traced bound: a
run is up to ``RUN`` consecutive tiles of one expert (:func:`_runs`), and a
run's weight blocks are picked by its expert's index from a scalar-prefetched
vector, so a step DMAs one expert's ``(D, 2F)`` gate-and-up block and its
``(F, D)`` down block (consecutive runs of one expert fetch nothing) and an
expert no row reached is never read. A step computes ``(silu(x W_g) * x W_u)
W_d`` with float32 accumulation ONCE over its run's rows: the MXUs' cost is
mostly a weight block's pushes, not the rows', so a run of three tiles costs
about two tiles' time where three products cost three, and stays hidden
under the expert's DMA. A run of one tile (a plain
tick's every expert) is the 16-row product; any longer run the one wider
product over ``RUN * TILE`` rows, of which only the run's own tiles are
written. Rows past the bound are not written and hold whatever the buffer
held: the caller masks them.

The rows reach a step as ``RUN`` tile operands on ``x`` (operand ``j`` moves
only when a run has a tile ``j``: a plain tick fetches one tile a step) and
leave it by the body's own 16-row copies from a two-slot buffer, a run's
waited for while the next run is multiplied: a blocked output of ``RUN``
tiles would write a short run's neighbours.

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

__all__ = ["TILE", "RUN", "grouped_swiglu", "product_steps"]

#: rows a tile: the bf16 sublane tile, so a tile is one packed vreg row block
TILE = 16
#: the most tiles one product multiplies: ``RUN * TILE`` rows are the MXU's
RUN = 8
F32 = jnp.float32


def product_steps(tiles):
    """Grid steps the kernel runs for experts of ``tiles`` tiles each."""
    return jnp.sum(-(-tiles // RUN))


def _runs(tile_expert, total):
    """The grid's schedule from the layout's: tiles ``< total`` of one
    expert, ``RUN`` at a time. Returns ``(steps, expert, first, count,
    tile_of)``: the traced number of runs, and a run's expert, first tile and
    tile count, each ``(n_tiles,)`` int32, whole up to ``steps``; ``tile_of``
    ``(RUN, n_tiles)`` is the tile operand ``j`` of ``x`` holds at a step:
    the run's tile ``j`` if it has one, else the tile it held before (no
    fetch)."""
    s = jnp.arange(tile_expert.shape[0], dtype=jnp.int32)
    live = s < total
    # prefixes as sums over a (tile, tile) comparison: one fusion each (a
    # cumsum is seven small operations on the chip)
    earlier = live[None] & (s[None] < s[:, None])
    place = (earlier & (tile_expert[None] == tile_expert[:, None])).sum(
        axis=1, dtype=jnp.int32)                 # within its expert's tiles
    begins = live & (place % RUN == 0)
    run_of = (begins[None] & (s[None] <= s[:, None])).sum(
        axis=1, dtype=jnp.int32) - 1
    mine = live[None] & (run_of[None] == s[:, None])            # (run, tile)
    count = mine.sum(axis=1, dtype=jnp.int32)
    opens = mine & begins[None]                          # a run's first tile
    first = jnp.where(opens, s[None], 0).sum(axis=1)
    expert = jnp.where(opens, tile_expert[None], 0).sum(axis=1)
    j = jnp.arange(RUN, dtype=jnp.int32)[:, None, None]
    tile_of = jnp.where((j < count[None, None]) & (s[None] <= s[:, None]),
                        first[None, None] + j, 0).max(axis=2)
    return begins.sum(dtype=jnp.int32), expert, first, count, tile_of


def _swiglu(x, gu_ref, dn_ref):
    gu = jnp.dot(x, gu_ref[0], preferred_element_type=F32)
    f = gu.shape[1] // 2
    h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    return jnp.dot(h.astype(x.dtype), dn_ref[0], preferred_element_type=F32)


def _relu2(x, up_ref, dn_ref):
    h = jnp.maximum(jnp.dot(x, up_ref[0], preferred_element_type=F32), 0.0)
    return jnp.dot((h * h).astype(x.dtype), dn_ref[0],
                   preferred_element_type=F32)


def _experts_kernel(steps_ref, expert_ref, first_ref, count_ref, tile_ref,
                    gu_ref, dn_ref, *rest, product):
    """One grid step = run ``s``: ``count_ref[s]`` tiles from ``first_ref[s]``
    of expert ``expert_ref[s]``, ``rest[:RUN]`` their ``(TILE, D)`` blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tiles, o_ref, buf, sem = rest[:RUN], *rest[RUN:]
    s = pl.program_id(0)
    n, slot = count_ref[s], s % 2

    def copy(run, slot_, j):
        """Tile ``j`` of run ``run`` out of ``slot_``, to its rows."""
        return pltpu.make_async_copy(
            buf.at[slot_, pl.ds(j * TILE, TILE)],
            o_ref.at[pl.ds((first_ref[run] + j) * TILE, TILE)],
            sem.at[slot_])

    @pl.when(n == 1)
    def _one_tile():
        buf[slot, pl.ds(0, TILE)] = product(tiles[0][...], gu_ref, dn_ref)

    @pl.when(n > 1)
    def _run():
        x = jnp.concatenate([t[...] for t in tiles], axis=0)
        buf[slot] = product(x, gu_ref, dn_ref)

    for j in range(RUN):
        pl.when(j < n)(copy(s, slot, j).start)
    # the run before was copied out while this one was multiplied; the last
    # run waits for its own
    before = jnp.maximum(s - 1, 0)
    for j in range(RUN):
        pl.when((s > 0) & (j < count_ref[before]))(
            copy(before, 1 - slot, j).wait)
        pl.when((s == steps_ref[0] - 1) & (j < n))(copy(s, slot, j).wait)


@functools.partial(jax.jit, static_argnames=("interpret", "gated"))
def _moe_experts_call(tile_expert, total, x, gate_up, down, *, interpret,
                      gated=True):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = x.shape
    steps, expert, first, count, tile_of = _runs(tile_expert, total)

    def weights(s, steps_, expert_, *_):
        return (expert_[s], 0, 0)

    def tile(j):
        return lambda s, steps_, expert_, first_, count_, tile_: (
            tile_[j, s], 0)

    call = pl.pallas_call(
        functools.partial(_experts_kernel,
                          product=_swiglu if gated else _relu2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, D, gate_up.shape[2]), weights),
                pl.BlockSpec((1, down.shape[1], D), weights),
                *(pl.BlockSpec((TILE, D), tile(j)) for j in range(RUN))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, RUN * TILE, D), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((R, D), F32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_pa._VMEM_LIMIT_BYTES),
        interpret=interpret)
    return call(steps[None], expert, first, count, tile_of, gate_up, down,
                *[x] * RUN)


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
