"""Micro-op timing for the GBDT iteration's device ops.

Times each candidate hot op standalone at HIGGS-like scale so the
per-iteration cost model is grounded in
measured per-op numbers instead of the summed-kernel guess:

  * level histogram (Pallas kernel) per level at several node counts
  * bottom-level leaf ``segment_sum`` (the scatter XLA lowers)
  * row routing via ``take_along_axis`` vs one-hot multiply-sum
  * objective grad/hess
  * score update gather

Usage: python scripts/prof_gbdt_microops.py [n_rows]  (default 4e6)
Prints one JSON line per op: {"op": ..., "ms": ..., "best_of": N}.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, reps=5):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        # tpulint: disable=TPU001 — micro-benchmark: the per-rep fence IS
        # the measurement (min-of-reps wall time per op)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, reps


def main():
    n = int(float(sys.argv[1])) if len(sys.argv) > 1 else 4_000_000
    F, B = 28, 256
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.objectives import get_objective
    from mmlspark_tpu.ops.pallas_kernels import level_histogram_pallas

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.integers(1, B, (n, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    scores = jnp.zeros(n, jnp.float32)
    jax.block_until_ready((xb, g, h, w, y))

    def emit(op, ms, reps, **kw):
        print(json.dumps({"op": op, "ms": round(ms, 2), "best_of": reps,
                          "n_rows": n, "platform": platform, **kw}),
              flush=True)

    # per-level histogram at the node counts a depth-5 tree visits
    for nodes in (1, 4, 16):
        node_rel = jnp.asarray(rng.integers(0, nodes, n, dtype=np.int32))
        ms, reps = timed(
            lambda nr=node_rel, nn=nodes: level_histogram_pallas(
                xb, nr, g, h, w, nn, B), reps=3)
        emit("pallas_hist", ms, reps, nodes=nodes)

    # bottom-level leaf stats: segment_sum over 32 leaves (current) ...
    node32 = jnp.asarray(rng.integers(0, 32, n, dtype=np.int32))

    @jax.jit
    def leaf_segsum(nr, g_, h_):
        data = jnp.stack([g_, h_], axis=-1)
        return jax.ops.segment_sum(data, nr, num_segments=32)

    ms, reps = timed(leaf_segsum, node32, g, h)
    emit("leaf_segment_sum", ms, reps)

    # ... vs a one-hot matmul formulation of the same reduction
    @jax.jit
    def leaf_onehot(nr, g_, h_):
        oh = jax.nn.one_hot(nr, 32, dtype=jnp.float32)     # (n, 32)
        return jnp.stack([g_ @ oh, h_ @ oh], axis=-1)

    ms, reps = timed(leaf_onehot, node32, g, h)
    emit("leaf_onehot_matmul", ms, reps)

    # row routing: per-row dynamic column gather (current) ...
    bf = jnp.asarray(rng.integers(0, F, 16, dtype=np.int32))
    node16 = jnp.asarray(rng.integers(0, 16, n, dtype=np.int32))

    @jax.jit
    def route_gather(nr, bf_):
        row_feat = jnp.clip(bf_[nr], 0, F - 1)
        return jnp.take_along_axis(
            xb, row_feat[:, None].astype(jnp.int32), axis=1)[:, 0] \
            .astype(jnp.int32)

    ms, reps = timed(route_gather, node16, bf)
    emit("route_take_along_axis", ms, reps)

    # ... vs one-hot multiply-sum over the 28 feature lanes
    @jax.jit
    def route_onehot(nr, bf_):
        row_feat = jnp.clip(bf_[nr], 0, F - 1)
        oh = jax.nn.one_hot(row_feat, F, dtype=jnp.float32)  # (n, F)
        return (xb.astype(jnp.float32) * oh).sum(axis=1).astype(jnp.int32)

    ms, reps = timed(route_onehot, node16, bf)
    emit("route_onehot_sum", ms, reps)

    # objective grad/hess (binary logloss)
    obj = get_objective("binary", num_class=1, alpha=0.9,
                        tweedie_variance_power=1.5)
    grad_fn = jax.jit(obj.grad_hess)
    ms, reps = timed(grad_fn, scores, y, w)
    emit("grad_hess", ms, reps)

    # score update: leaf-value gather + add
    leaf_val = jnp.asarray(rng.normal(size=32).astype(np.float32))

    @jax.jit
    def score_update(s, lv, nr):
        return s + jnp.take(lv, nr) * 0.1

    ms, reps = timed(score_update, scores, leaf_val, node32)
    emit("score_update", ms, reps)


if __name__ == "__main__":
    main()
