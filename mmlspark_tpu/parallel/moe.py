"""Expert parallelism: mixture-of-experts FFN with all_to_all routing.

Beyond-parity distributed capability (the reference has no intra-model
sharding at all — SURVEY §2.8): a GShard-style top-1 MoE block whose experts
are sharded over an ``ep`` mesh axis. Tokens are locally gated, packed into
per-expert capacity slots, exchanged with ``jax.lax.all_to_all`` (which XLA
lowers onto ICI), processed by the local experts, and returned the same way.

Design notes (TPU-first):
* dispatch/combine are einsums over one-hot masks — MXU work, no scatters;
* static capacity ``C`` keeps every shape fixed for XLA (overflow tokens are
  dropped, standard GShard semantics, exposed via ``aux["dropped"]``);
* the block is written for ``shard_map`` (see :func:`moe_ffn_sharded`) so
  the collective pattern is explicit and testable on a virtual CPU mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["init_moe_params", "moe_ffn_local", "moe_ffn_sharded",
           "moe_ffn_gspmd", "moe_shardings", "moe_capacity",
           "route_topk", "moe_topk_held", "held_tiles", "MOE_STATS"]


def moe_capacity(tokens_per_shard: int, n_experts: int,
                 capacity_factor: float = 1.25) -> int:
    """Static per-expert capacity per source shard."""
    return max(1, math.ceil(tokens_per_shard / n_experts * capacity_factor))


def init_moe_params(d_model: int, d_ff: int, n_experts: int,
                    seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_ff)
    return {
        "gate": (rng.normal(0, s1, (d_model, n_experts))).astype(np.float32),
        "w1": (rng.normal(0, s1, (n_experts, d_model, d_ff))).astype(np.float32),
        "b1": np.zeros((n_experts, d_ff), np.float32),
        "w2": (rng.normal(0, s2, (n_experts, d_ff, d_model))).astype(np.float32),
        "b2": np.zeros((n_experts, d_model), np.float32),
    }


def moe_shardings(mesh: Mesh, ep_axis: str = "ep") -> Dict:
    """Experts sharded over the ep axis; the gate replicated."""
    return {
        "gate": NamedSharding(mesh, P()),
        "w1": NamedSharding(mesh, P(ep_axis, None, None)),
        "b1": NamedSharding(mesh, P(ep_axis, None)),
        "w2": NamedSharding(mesh, P(ep_axis, None, None)),
        "b2": NamedSharding(mesh, P(ep_axis, None)),
    }


def _route_and_pack(x, gate_w, n_experts: int, capacity: int):
    """Core top-1 routing + capacity packing for one token group.
    x (T, D) → slot (T, E, C), gate_prob (T,), onehot (T, E), probs (T, E).
    The single source of truth — every MoE variant (local / shard_map /
    GSPMD-grouped) builds on this."""
    logits = x @ gate_w.astype(x.dtype)                     # (T, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # fp32 router
    expert = jnp.argmax(probs, axis=-1)                     # (T,)
    gate_prob = jnp.max(probs, axis=-1)                     # (T,)
    onehot = jax.nn.one_hot(expert, n_experts,
                            dtype=jnp.float32)              # (T, E)
    # position of each token within its expert's slots, in token order
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0         # (T, E)
    keep = (pos < capacity) & (onehot > 0)
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * \
        keep[..., None]                                     # (T, E, C)
    return slot, gate_prob, onehot, probs


def _aux_from_routing(slot, onehot, probs, n_experts: int,
                      token_axis: int = -2):
    """Shared auxiliaries: dropped-token count and the Switch/GShard
    load-balance loss E·Σₑ fₑ·Pₑ (fraction routed × mean router prob;
    without it top-1 routing classically collapses onto one expert and
    over-capacity tokens are silently zeroed)."""
    frac_routed = jnp.mean(onehot, axis=token_axis)
    mean_prob = jnp.mean(probs, axis=token_axis)
    return {"dropped": jnp.sum(onehot) - jnp.sum(slot),
            "balance_loss": n_experts * jnp.mean(
                jnp.sum(frac_routed * mean_prob, axis=-1))}


def _gate_and_dispatch(x, gate_w, n_experts: int, capacity: int):
    """Top-1 gating + capacity packing. x (T, D) → slot, probs, aux."""
    slot, gate_prob, onehot, probs = _route_and_pack(
        x, gate_w, n_experts, capacity)
    return slot, gate_prob, _aux_from_routing(slot, onehot, probs, n_experts)


def moe_ffn_local(x, params, n_experts: int, capacity: int):
    """Single-device reference MoE (no collectives): x (T, D) → (T, D).
    Returns (y, aux) with aux = {dropped, balance_loss}."""
    slot, gate_prob, aux = _gate_and_dispatch(
        x, params["gate"], n_experts, capacity)
    expert_in = jnp.einsum("tec,td->ecd", slot,
                           x.astype(jnp.float32))           # (E, C, D)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w1"])
                    + params["b1"][:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"]) \
        + params["b2"][:, None, :]                          # (E, C, D)
    y = jnp.einsum("ecd,tec->td", out, slot)                # (T, D)
    return (y * gate_prob[:, None]).astype(x.dtype), aux


def _moe_shard_body(x_local, gate_w, w1_local, b1_local, w2_local, b2_local,
                    *, n_experts: int, capacity: int, ep_axis: str):
    """Per-shard body under shard_map: local gating, all_to_all dispatch to
    the expert owners, expert FFN, all_to_all combine back."""
    ep = jax.lax.axis_size(ep_axis)
    e_local = n_experts // ep
    slot, gate_prob, aux = _gate_and_dispatch(
        x_local, gate_w, n_experts, capacity)
    D = x_local.shape[-1]
    dispatch = jnp.einsum("tec,td->ecd", slot,
                          x_local.astype(jnp.float32))      # (E, C, D)
    dispatch = dispatch.reshape(ep, e_local, capacity, D)
    # symmetric exchange (split=concat=0 is its own transpose, so autodiff
    # reuses the same collective): shard k gets its e_local experts' slots
    # from every source shard — axis 0 becomes the source shard
    expert_in = jax.lax.all_to_all(dispatch, ep_axis,
                                   split_axis=0, concat_axis=0)
    expert_in = jnp.transpose(expert_in, (1, 0, 2, 3)) \
        .reshape(e_local, ep * capacity, D)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, w1_local)
                    + b1_local[:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, w2_local) \
        + b2_local[:, None, :]                              # (e_local, ep*C, D)
    # inverse exchange: back to (E, C, D) on the token-owning shard
    out = jnp.transpose(out.reshape(e_local, ep, capacity, D), (1, 0, 2, 3))
    returned = jax.lax.all_to_all(out, ep_axis,
                                  split_axis=0, concat_axis=0)
    returned = returned.reshape(n_experts, capacity, D)
    y = jnp.einsum("ecd,tec->td", returned, slot)
    aux = {"dropped": jax.lax.psum(aux["dropped"], ep_axis),
           "balance_loss": jax.lax.pmean(aux["balance_loss"], ep_axis)}
    return (y * gate_prob[:, None]).astype(x_local.dtype), aux


def moe_ffn_sharded(x, params, mesh: Mesh, n_experts: int,
                    capacity: int, ep_axis: str = "ep") -> Tuple:
    """Expert-parallel MoE over ``mesh[ep_axis]``.

    ``x`` (T, D) is sharded over tokens on the ep axis; expert weights are
    sharded over experts on the same axis (GShard: the data and expert
    meshes coincide). Returns (y, aux) with aux = {dropped, balance_loss}.
    """
    from .mesh import get_shard_map
    shard_map, _ = get_shard_map()

    assert n_experts % mesh.shape[ep_axis] == 0, \
        f"n_experts {n_experts} not divisible by ep={mesh.shape[ep_axis]}"
    body = partial(_moe_shard_body, n_experts=n_experts, capacity=capacity,
                   ep_axis=ep_axis)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(ep_axis, None), P(), P(ep_axis, None, None),
                  P(ep_axis, None), P(ep_axis, None, None), P(ep_axis, None)),
        out_specs=(P(ep_axis, None), P()),
    )(x, params["gate"], params["w1"], params["b1"],
      params["w2"], params["b2"])


def _group_gate_and_dispatch(t, gate_w, n_experts: int, capacity: int):
    """Grouped gating: t (G, Tg, D) → slot (G, Tg, E, C), probs, aux.
    vmap of the core packer over groups — capacity is per (group, expert),
    so the cumsum stays group-local (the GShard grouping trick that keeps
    dispatch free of cross-shard scans)."""
    slot, gate_prob, onehot, probs = jax.vmap(
        partial(_route_and_pack, n_experts=n_experts, capacity=capacity),
        in_axes=(0, None))(t, gate_w)
    return slot, gate_prob, _aux_from_routing(slot, onehot, probs, n_experts)


def moe_ffn_gspmd(t, params, n_experts: int, capacity: int,
                  mesh: Mesh = None, ep_axis: str = "dp",
                  tp_axis: str = None):
    """GSPMD-style expert parallelism: no shard_map — sharding constraints
    express the layout changes and XLA inserts the all-to-alls over ICI.

    ``t`` (G, Tg, D): groups sharded over ``ep_axis`` (in a transformer the
    batch axis is the natural group axis, so ep coincides with dp — the
    GShard deployment). Expert weights (E, ...) are sharded over the same
    axis; ``tp_axis`` additionally shards each expert's hidden dim. This
    variant composes with constraint-style models (zoo transformer); the
    ``shard_map`` variant (:func:`moe_ffn_sharded`) is the explicit-
    collective equivalent used where the mesh is handled manually.
    """
    def constrain(v, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(*spec)))
        return v

    t = constrain(t, ep_axis, None, None)
    slot, gate_prob, aux = _group_gate_and_dispatch(
        t, params["gate"], n_experts, capacity)
    # expert compute and the cross-device dispatch run in the model dtype
    # (bf16 halves the all-to-all bytes and rides the MXU fast path);
    # only the router softmax above stays fp32, GShard practice
    dt = t.dtype
    slot_dt = slot.astype(dt)
    dispatch = jnp.einsum("gtec,gtd->gecd", slot_dt, t)     # (G, E, C, D)
    # groups-sharded → experts-sharded: XLA lowers this re-shard to an
    # all-to-all over ep_axis
    dispatch = constrain(dispatch, None, ep_axis, None, None)
    h = jax.nn.gelu(
        jnp.einsum("gecd,edf->gecf", dispatch, params["w1"].astype(dt))
        + params["b1"].astype(dt)[None, :, None, :])
    if tp_axis is not None:
        h = constrain(h, None, ep_axis, None, tp_axis)
    out = jnp.einsum("gecf,efd->gecd", h, params["w2"].astype(dt)) \
        + params["b2"].astype(dt)[None, :, None, :]
    # experts-sharded → groups-sharded: the return all-to-all
    out = constrain(out, ep_axis, None, None, None)
    y = jnp.einsum("gecd,gtec->gtd", out, slot_dt)
    return y * gate_prob[..., None].astype(dt), aux


# ---- top-k, dropless, over the experts held ---------------------------------
# The serving path of a routed feed-forward whose layer is shared by expert
# parallelism (``TransformerConfig.routed``): this process routes every token
# over ALL the experts, keeps the (token, expert) pairs that land on the
# experts it holds and adds up their part of the result; the other shares'
# parts are theirs to add (across chips an exchange sums them; on one chip
# the layer runs without it). No capacity: a pair is never dropped.

#: what :func:`moe_topk_held` counts, in the order of its ``stats`` vector
MOE_STATS = ("pairs_routed", "pairs_held", "pairs_dropped",
             "pairs_misplaced", "experts_touched", "tiles", "product_steps",
             "expert_load_max")


def route_topk(x32, router_w, bias, spec):
    """Group-limited top-k routing, float32 throughout. ``x32`` (T, D)
    float32, ``router_w`` (D, experts), ``bias`` (experts,) the selection
    bias. Returns ``(idx, weight)``, both (T, per_token): the chosen experts
    and ``scale * s / sum(chosen s)`` of their UNBIASED sigmoid scores.
    With ``spec.score == "softmax"`` the router is the linear map alone
    (``bias`` None): the largest LOGITS are chosen and the weights are
    ``scale`` times a softmax over the chosen logits."""
    f32 = jnp.float32
    s = jnp.dot(x32.astype(f32), router_w.astype(f32),
                precision=jax.lax.Precision.HIGHEST)
    softmax = spec.score == "softmax"
    if not softmax:
        s = jax.nn.sigmoid(s)
    sel = s if bias is None else s + bias.astype(f32)
    T, E = s.shape
    G = spec.groups
    per = E // G
    # one group of logits has no group to drop (the sigmoid routers keep
    # the step at one group too: their programs stay as they were)
    if G > 1 or not softmax:
        best2 = jax.lax.top_k(sel.reshape(T, G, per), min(2, per))[0].sum(-1)
        _, keep = jax.lax.top_k(best2, spec.groups_kept)        # (T, kept)
        kept = (keep[:, :, None] == jnp.arange(G)[None, None]).any(axis=1)
        sel = jnp.where(jnp.repeat(kept, per, axis=1), sel, -jnp.inf)
    _, idx = jax.lax.top_k(sel, spec.per_token)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    weight = (jax.nn.softmax(chosen, axis=-1) if softmax
              else chosen / chosen.sum(axis=-1, keepdims=True)) * spec.scale
    return idx.astype(jnp.int32), weight


def held_tiles(pairs: int, held: int, tile: int) -> int:
    """The most tiles ``pairs`` (token, expert) pairs over ``held`` experts
    can fill: each expert's rows round up to whole tiles."""
    return min(held, pairs) + pairs // tile


def moe_topk_held(x, x32, p, spec, valid, interpret=None):
    """The held experts' part of a routed feed-forward, plus the shared
    expert. ``x`` (T, D) in the compute dtype, ``x32`` the same rows in
    float32 (the router reads these: a rounded input swaps near-tied
    experts), ``valid`` (T,) bool the real tokens (padding and idle rows
    route nowhere and read no expert). ``p``: ``router.w`` (D, experts),
    ``bias`` (experts,; none under a softmax router), ``experts.gate_up``
    (held, D, 2F), ``experts.down`` (held, F, D), and
    ``shared.{gate,up,down}`` when the layer has a shared
    expert. Returns ``(y (T, D) in x's dtype, stats int32[8])``, the stats
    in :data:`MOE_STATS`' order: the live tokens' pairs over all experts,
    those on held experts, held pairs given no row (0: no capacity), pairs
    whose row lies in a tile of ANOTHER expert's weights (0: the layout's
    own check), distinct held experts with a pair, the tiles in use, the
    grid steps the product ran (an expert's run of tiles is one step, up to
    ``ops.grouped_matmul.RUN`` of them: tiles over steps is how many tiles a
    product folds), the largest expert's pairs.

    The pairs on held experts are ranked within their expert by a running
    count (no sort is needed for that), laid out expert after expert in
    tiles of ``ops.grouped_matmul.TILE`` rows, multiplied by
    :func:`~mmlspark_tpu.ops.grouped_matmul.grouped_swiglu`, which reads
    only the experts that have a tile, and weighted back onto their tokens.
    Rows are moved by products with 0/1 matrices (a gather of thousands of
    small rows is a sequential loop on the chip).

    With ``spec.latent`` the experts live in a latent all of them share:
    ``p["to_latent"].w`` (D, L) is applied BEFORE the layout (the rows laid
    out are ``L`` wide), ``p["from_latent"].w`` (L, D) AFTER the weighted
    sum (linear, so the shares' results still add); the router and the
    shared expert read the ``D``-wide row. ``spec.form == "relu2"``:
    ``experts.up`` (held, L, F) in ``gate_up``'s place, an expert
    ``relu(l W_1)^2 W_2``, the shared expert ``shared.{up,down}`` alike."""
    from ..ops.grouped_matmul import (TILE, grouped_swiglu,
                                      product_steps)
    f32 = jnp.float32
    T, D = x.shape
    k, Eh = spec.per_token, spec.held
    # a row that is no token holds whatever its mixer left (an idle row's
    # context is no context): the placement products below sum over every
    # row, and 0 x NaN is NaN, so such a row is 0 before it meets another
    x = jnp.where(valid[:, None], x, jnp.zeros((), x.dtype))
    x32 = jnp.where(valid[:, None], x32, 0.0)
    idx, weight = route_topk(x32, p["router"]["w"], p.get("bias"), spec)
    local = idx - spec.first
    held = (local >= 0) & (local < Eh) & valid[:, None]         # (T, k)
    e = jnp.where(held, local, Eh).reshape(T * k)
    onehot = e[:, None] == jnp.arange(Eh, dtype=jnp.int32)[None]   # (P, Eh)
    counts = onehot.sum(axis=0, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
    tiles = -(-counts // TILE)
    ends = jnp.cumsum(tiles)
    first_row = (ends - tiles) * TILE
    n_tiles = held_tiles(T * k, Eh, TILE)
    R = n_tiles * TILE
    row = jnp.where(onehot, rank + first_row[None], 0).sum(axis=1)
    row = jnp.where(held.reshape(-1), row, R).reshape(T, k)     # R: nowhere
    tile_expert = jnp.minimum(jnp.sum(
        jnp.arange(n_tiles, dtype=jnp.int32)[:, None] >= ends[None], axis=1),
        Eh - 1).astype(jnp.int32)
    total = ends[-1]
    rows = jnp.arange(R, dtype=jnp.int32)
    place = row[None] == rows[:, None, None]                    # (R, T, k)
    gated = spec.form != "relu2"
    xl = x @ p["to_latent"]["w"].astype(x.dtype) if spec.latent else x
    xs = jnp.dot(place.any(axis=2).astype(x.dtype), xl)         # (R, D | L)
    ys = grouped_swiglu(xs, tile_expert, total,
                        p["experts"]["gate_up" if gated else "up"],
                        p["experts"]["down"], interpret=interpret,
                        gated=gated)
    # a tile past the bound was not written: whatever the buffer held
    ys = jnp.where((rows < total * TILE)[:, None], ys, 0.0)
    back = jnp.where(place, weight[None], 0.0).sum(axis=2)      # (R, T)
    y = jnp.einsum("rt,rd->td", back, ys,
                   precision=jax.lax.Precision.HIGHEST)
    if spec.latent:
        y = jnp.matmul(y.astype(x.dtype),
                       p["from_latent"]["w"].astype(x.dtype),
                       preferred_element_type=f32)
    if "shared" in p:
        sh = p["shared"]
        dt = x.dtype
        hid = (jax.nn.silu(x @ sh["gate"]["w"].astype(dt))
               * (x @ sh["up"]["w"].astype(dt)) if gated
               else jnp.square(jax.nn.relu(x @ sh["up"]["w"].astype(dt))))
        y = y + jnp.matmul(hid, sh["down"]["w"].astype(dt),
                           preferred_element_type=f32)
    n_held = held.sum(dtype=jnp.int32)
    placed = row < R
    reads = (jnp.minimum(row, R - 1)[:, :, None] // TILE
             == jnp.arange(n_tiles)[None, None]) @ tile_expert  # (T, k)
    stats = jnp.stack([
        valid.sum(dtype=jnp.int32) * k, n_held,
        n_held - placed.sum(dtype=jnp.int32),
        (placed & (reads != local)).sum(dtype=jnp.int32),
        (counts > 0).sum(dtype=jnp.int32), total, product_steps(tiles),
        counts.max()])
    return y.astype(x.dtype), stats
