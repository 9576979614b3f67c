"""On-chip microbench: Pallas MXU histogram vs the XLA segment_sum fallback.

The GBDT hot loop's histogram build is the TPU answer to LightGBM's C++
scatter-add (reached via ``LGBM_BoosterUpdateOneIter``,
``lightgbm/.../booster/LightGBMBooster.scala:351-361``). Prints one JSON
line per config with both builders' ms/level and the speedup. Run on the
real chip: ``python scripts/bench_pallas_hist.py``.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def time_fn(fn, xb, node, g, h, w, **kw):
    """Dependency-chained timing that needs no per-call completion signal.

    A per-call sync costs a host round trip that would swamp a fast
    kernel. So: dispatch L builder calls where call i+1's
    gradients data-depend on call i's histogram (no elision, strictly
    sequential on device), then force ONE scalar fetch that depends on the
    last call — the fetch cannot complete before all L executions have.
    """
    import jax
    import jax.numpy as jnp

    bump = jax.jit(lambda g, hist: g + hist[0, 0, 0, 0] * 1e-30)
    tail = jax.jit(lambda hist: jnp.sum(hist[0, 0, :2, 0]))

    def chain(length):
        gc = g
        hist = None
        for _ in range(length):
            hist = fn(xb, node, gc, h, w, **kw)
            gc = bump(gc, hist)
        return float(tail(hist))

    L = 6
    chain(1)  # compile everything
    t0 = time.perf_counter()
    chain(L)
    total = time.perf_counter() - t0
    return max((total - _rtt_baseline()) / L, 1e-9)


_RTT = [None]


def _rtt_baseline():
    """Dispatch+fetch cost of a trivial program — the constant to subtract
    from loop timings."""
    if _RTT[0] is None:
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: x + 1.0)
        float(f(jnp.float32(0.0)))
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(f(jnp.float32(1.0)))
            ts.append(time.perf_counter() - t0)
        _RTT[0] = min(ts)
    return _RTT[0]


def segment_sum_hist(xb, node_rel, g, h, w, n_nodes, n_bins):
    import jax
    import jax.numpy as jnp

    data = jnp.stack([g, h, w], axis=-1)

    def per_feature(bins_col):
        seg = node_rel * n_bins + bins_col.astype(jnp.int32)
        return jax.ops.segment_sum(data, seg, num_segments=n_nodes * n_bins)

    hist = jax.vmap(per_feature, in_axes=1)(xb)
    return jnp.transpose(hist.reshape(xb.shape[1], n_nodes, n_bins, 3),
                         (1, 0, 2, 3))


def main():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.pallas_kernels import level_histogram_pallas

    from mmlspark_tpu.utils.device import is_tpu
    backend = jax.default_backend()
    on_tpu = is_tpu()
    seg_jit = jax.jit(segment_sum_hist,
                      static_argnames=("n_nodes", "n_bins"))

    rng = np.random.default_rng(0)
    results = []
    # default: a full level sweep (levels 0-6 = 1..64 nodes) at 1M rows plus
    # the OOM-class 4M configs; BENCH_ROWS / BENCH_NODES scope a run so it
    # fits a call's time limit
    rows = [int(r) for r in os.environ.get(
        "BENCH_ROWS", "1000000,4000000").split(",")]
    nodes_for = {1_000_000: [1, 2, 4, 8, 16, 32, 64], 4_000_000: [8, 32]}
    if os.environ.get("BENCH_NODES"):
        nd = [int(x) for x in os.environ["BENCH_NODES"].split(",")]
        nodes_for = {r: nd for r in rows}
    configs = [(n, 28, nn, 255) for n in rows
               for nn in nodes_for.get(n, [8, 32])]
    for n, F, n_nodes, n_bins in configs:
        xb = jnp.asarray(rng.integers(0, n_bins, (n, F), dtype=np.int32))
        node = jnp.asarray(rng.integers(0, n_nodes, n, dtype=np.int32))
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.asarray(np.abs(rng.normal(size=n)).astype(np.float32))
        w = jnp.ones(n, dtype=jnp.float32)

        rec = {"metric": "gbdt_level_histogram_ms",
               "n": n, "features": F, "nodes": n_nodes, "bins": n_bins,
               # per-op cost from a dependency-chained mean inside ONE
               # window (per-rep fences would cost ~RTT each); a window
               # artifact shows up as disagreement with the neighboring
               # rows of the same sweep
               "timing": "dependency-chain-mean",
               "platform": backend}
        try:
            t_pal = time_fn(level_histogram_pallas, xb, node, g, h, w,
                            n_nodes=n_nodes, n_bins=n_bins,
                            interpret=not on_tpu)
            rec["pallas_ms"] = round(t_pal * 1e3, 2)
        except Exception as e:
            rec["pallas_error"] = str(e).splitlines()[0][:120]
            t_pal = None
        try:
            t_seg = time_fn(seg_jit, xb, node, g, h, w,
                            n_nodes=n_nodes, n_bins=n_bins)
            rec["segment_sum_ms"] = round(t_seg * 1e3, 2)
        except Exception as e:
            # the vmapped segment_sum materializes an (F, n, 3) temp and can
            # blow HBM at HIGGS scale — that is the kernel's reason to exist
            rec["segment_sum_error"] = str(e).splitlines()[0][:120]
            t_seg = None
        if t_pal and t_seg:
            rec["speedup"] = round(t_seg / t_pal, 2)
            a = np.asarray(seg_jit(xb, node, g, h, w,
                                   n_nodes=n_nodes, n_bins=n_bins))
            b = np.asarray(level_histogram_pallas(
                xb, node, g, h, w, n_nodes=n_nodes, n_bins=n_bins,
                interpret=not on_tpu))
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
        results.append(rec)
        print(json.dumps(rec), flush=True)
    return results


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
