"""On-chip SPMD check: ONNXModel ``mesh_sharded`` mode vs plain mode.

Round-3 verdict item 8: the mesh-mode SPMD path had only ever executed on
the virtual 8-CPU mesh; running it on a 1-device mesh on the REAL chip
retires its compile risk (GSPMD partitioning + sharding annotations compile
for the TPU target even when the mesh is trivial). Multi-device correctness
stays pinned by the CPU-mesh tests; this records mesh-mode img/s ≈
non-mesh img/s on hardware. One JSON line.

Parity anchor: the reference's per-partition ORT session placement
(``deep-learning/.../onnx/ONNXModel.scala:293-303``); here placement is a
``jax.sharding`` annotation over a Mesh instead of a device id.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = os.environ.get("BENCH_SCALE", "") == "small"


def main():
    if SMALL:
        from mmlspark_tpu.utils.device import force_cpu
        jax = force_cpu()
    else:
        import jax

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.zoo.resnet import ResNetConfig, \
        export_resnet_onnx
    from mmlspark_tpu.parallel.mesh import MeshContext

    batch = int(os.environ.get("BENCH_BATCH", "16" if SMALL else "256"))
    rng = np.random.default_rng(0)
    cfg = ResNetConfig([2, 2, 2, 2], num_classes=200)
    model_bytes = export_resnet_onnx(cfg, seed=0)

    X = rng.integers(0, 256, (batch * 2, 64, 64, 3), dtype=np.uint8)
    col = np.empty(len(X), dtype=object)
    for i in range(len(X)):
        col[i] = X[i]
    df = DataFrame({"image": col})

    def build(mesh_sharded):
        return ONNXModel(model_bytes,
                         feed_dict={"input": "image"},
                         fetch_dict={"logits": "logits"},
                         argmax_dict={"pred": "logits"},
                         transpose_dict={"input": [0, 3, 1, 2]},
                         mini_batch_size=batch,
                         compute_dtype="bfloat16",
                         mesh_sharded=mesh_sharded)

    def timed_ips(m, ctx):
        with ctx:
            m.transform(df.head(batch))        # compile + first transfer
            t0 = time.perf_counter()
            out = m.transform(df)
            # DataFrame.transform materializes host-side numpy — the
            # fetch IS the fence
            assert len(out) == len(X)
            return round(len(X) / (time.perf_counter() - t0), 2)

    import contextlib

    # interleave the two modes and keep per-mode bests: two back-to-back
    # single runs would measure drift of the host feed, not the mesh-mode
    # overhead. Models build once; each round re-times the same transforms.
    rounds = int(os.environ.get("BENCH_MESH_ROUNDS", "3"))
    m_plain, m_mesh = build(False), build(True)
    plain_runs, mesh_runs = [], []
    for _ in range(rounds):
        plain_runs.append(timed_ips(m_plain, contextlib.nullcontext()))
        mesh_runs.append(timed_ips(m_mesh, MeshContext({"data": -1})))
    plain_ips, mesh_ips = max(plain_runs), max(mesh_runs)
    # the headline ratio uses per-mode MEDIANS: a single lucky link
    # window on one mode's best makes a best-vs-best ratio read as mode
    # overhead (the r5 campaign row's 0.653 was exactly that — medians of
    # the same runs said 0.96); best-of values stay for continuity.
    # statistics.median averages the middle pair — an upper-middle pick
    # would degenerate back to best-of at BENCH_MESH_ROUNDS=2
    from statistics import median
    ratio_med = (round(median(mesh_runs) / median(plain_runs), 3)
                 if median(plain_runs) else None)

    d = jax.devices()[0]
    print(json.dumps({
        "metric": "onnx_mesh_spmd_images_per_sec",
        "plain_ips": plain_ips,
        "mesh_ips": mesh_ips,
        "ratio": ratio_med,
        "ratio_best_of": round(mesh_ips / plain_ips, 3)
        if plain_ips else None,
        "plain_runs": plain_runs, "mesh_runs": mesh_runs,
        "n_devices": len(jax.devices()),
        "platform": d.platform, "device": d.device_kind}), flush=True)


if __name__ == "__main__":
    main()
