"""Benches for BASELINE.json configs #2-#4: BERT embeddings, ImageFeaturizer
transfer-learning, and explainer (repeated-inference) throughput.

Each prints one JSON line. Sized by env:
  BENCH_BERT_ROWS / BENCH_FEAT_ROWS / BENCH_SHAP_ROWS, BENCH_SCALE=small
(small = CPU-friendly shapes for smoke tests; default = benchmark shapes).

Run on the chip: ``python scripts/bench_configs.py [bert|featurizer|shap]``.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = os.environ.get("BENCH_SCALE", "") == "small"


def _bench_transform(model, df, n_rows, passes=3):
    """Best-of-N e2e rate + spread fields (every row carries them: a
    single outlier pass must be visible in the row itself)."""
    out = model.transform(df.head(min(8, n_rows)))  # warmup/compile
    assert len(out) > 0
    rates = []
    for _ in range(passes):
        t0 = time.perf_counter()
        out = model.transform(df)
        rates.append(n_rows / (time.perf_counter() - t0))
    assert len(out) == n_rows
    return {"value": round(max(rates), 2), "best_of": len(rates),
            "pass_spread": round((max(rates) - min(rates)) / max(rates), 3)}


def _device_resident_rate(onnx_model, feeds_np, reps=10):
    """Rows/sec once inputs are already on device — separates the chip from
    the host feed (same convention as the headline bench's
    ``device_resident_ips``). Fencing via a fetched scalar on the LAST
    dispatch (in-order execution fences the earlier ones)."""
    import jax
    import jax.numpy as jnp
    jitted = onnx_model._ensure_jitted()
    params = onnx_model._params_for_device(None)
    devs = {k: jax.device_put(v) for k, v in feeds_np.items()}
    n = next(iter(feeds_np.values())).shape[0]

    def tail(outs):
        leaf = jax.tree_util.tree_leaves(outs)[0]
        return float(jnp.sum(leaf.reshape(-1)[:2].astype(jnp.float32)))

    tail(jitted(params, devs))          # compile + warm
    t0 = time.perf_counter()
    outs = None
    for _ in range(reps):
        outs = jitted(params, devs)
    tail(outs)
    return round(n * reps / (time.perf_counter() - t0), 2)


def _device_resident_rate_fused(onnx_model, feeds_np, R=10, reps=3):
    """Fused-scan variant of ``_device_resident_rate``: R forwards inside
    ONE compiled program, each iteration's input data-dependent on the
    previous output (the carry perturbs one feed, so XLA cannot hoist the
    loop-invariant forward out of the scan) — the ~ms per-dispatch
    runtime floor amortizes R×. Same methodology and mean-of-reps
    estimator as the headline's ``device_resident_ips_fused``."""
    import jax
    import jax.numpy as jnp
    jitted = onnx_model._ensure_jitted()
    params = onnx_model._params_for_device(None)
    devs = {k: jax.device_put(v) for k, v in feeds_np.items()}
    n = next(iter(feeds_np.values())).shape[0]
    key0 = next(iter(feeds_np))     # first feed in caller order (BERT:
    #                                 ids, not the all-ones mask)

    @jax.jit
    def fused(params, devs):
        def body(t, _):
            f = dict(devs)
            x = f[key0]
            if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
                # uint8 pixels: xor the lowest bit — stays in range
                # (subtraction would wrap 0 -> 255 before any clamp)
                f[key0] = x ^ t.astype(x.dtype)
            elif jnp.issubdtype(x.dtype, jnp.integer):
                # token-id-safe perturbation: stays within [0, vocab)
                f[key0] = jnp.maximum(x - t.astype(x.dtype), 0)
            else:
                f[key0] = x + t.astype(x.dtype)
            outs = jitted(params, f)
            leaf = jax.tree_util.tree_leaves(outs)[0]
            nxt = (jnp.abs(leaf.reshape(-1)[0].astype(jnp.float32))
                   > 0).astype(jnp.int32)
            return nxt, None
        t, _ = jax.lax.scan(body, jnp.int32(0), None, length=R)
        return t

    int(fused(params, devs))                  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        int(fused(params, devs))              # fetched scalar = fence
    return round(n * R * reps / (time.perf_counter() - t0), 2)


def _fused_or_none(onnx_model, feeds_np, **kw):
    """Failure-tolerant wrapper (parity with bench.py's fused field): a
    scan-trace/compile failure must not abort the bench after the e2e and
    per-dispatch measurements already ran — the row ships with None."""
    try:
        return _device_resident_rate_fused(onnx_model, feeds_np, **kw)
    except Exception:                           # noqa: BLE001
        return None


def bench_bert():
    """Config #3: BERT-base-shaped sentence embeddings over a token column
    through the foreign-ONNX importer (torch-exporter-style graph)."""
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.zoo.bert_onnx import (BertOnnxConfig,
                                                   export_bert_onnx)

    if SMALL:
        cfg = BertOnnxConfig()
        n_rows, batch, seq = 32, 8, 64
    else:
        # BERT-base dimensions (vocab kept small: embedding lookup cost is
        # row-gather, invariant to vocab beyond cache effects)
        cfg = BertOnnxConfig(vocab=8192, layers=12, d_model=768, heads=12,
                             d_ff=3072, max_len=128)
        n_rows, batch, seq = 2048, 128, 128
    n_rows = int(os.environ.get("BENCH_BERT_ROWS", n_rows))
    rng = np.random.default_rng(0)
    model_bytes = export_bert_onnx(cfg, seed=0)
    # fetch the mean-pooled sentence embedding (B, D), not the full
    # (B, S, D) hidden states: a sentence-embedding pipeline only needs the
    # pooled vector, and the device→host transfer shrinks by S× (800 MB →
    # 6 MB at 2048×128×768)
    m = ONNXModel(model_bytes,
                  feed_dict={"input_ids": "ids", "attention_mask": "mask"},
                  fetch_dict={"emb": "pooled"},
                  mini_batch_size=batch, compute_dtype="bfloat16")
    ids = rng.integers(0, cfg.vocab, (n_rows, seq), dtype=np.int64)
    mask = np.ones((n_rows, seq), dtype=np.int64)
    df = DataFrame({"ids": [r for r in ids], "mask": [r for r in mask]})
    res = _bench_transform(m, df, n_rows)
    bert_feeds = {"input_ids": ids[:batch], "attention_mask": mask[:batch]}
    dev = _device_resident_rate(m, bert_feeds)
    dev_fused = _fused_or_none(m, bert_feeds)
    print(json.dumps({"metric": "bert_base_embeddings_seq_per_sec",
                      **res, "unit": "sequences/sec/chip",
                      "device_resident_sps": dev,
                      "device_resident_sps_fused": dev_fused,
                      "seq_len": seq, "layers": cfg.layers,
                      "d_model": cfg.d_model,
                      "platform": _platform()}), flush=True)


def bench_featurizer():
    """Config #4: ImageFeaturizer (ONNX backbone, cut layer) over images."""
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.featurizer import ImageFeaturizer
    from mmlspark_tpu.models.zoo.resnet import (RESNET18_CFG, RESNET50,
                                                export_resnet_onnx)

    cfg = RESNET18_CFG if SMALL else RESNET50
    n_rows = 16 if SMALL else 1024
    n_rows = int(os.environ.get("BENCH_FEAT_ROWS", n_rows))
    size = 64 if SMALL else 224
    rng = np.random.default_rng(0)
    feat = ImageFeaturizer(onnx_model=export_resnet_onnx(cfg, seed=0),
                           input_col="image", output_col="features",
                           input_size=size,
                           mini_batch_size=(8 if SMALL else 128))
    imgs = rng.integers(0, 256, (n_rows, size, size, 3), dtype=np.uint8)
    df = DataFrame({"image": [i for i in imgs]})
    res = _bench_transform(feat, df, n_rows)
    # device-resident: the inner backbone on a pre-staged uint8 batch with
    # the same on-device transpose+normalize prep the e2e path uses
    inner = feat._inner()
    feed_name = list(inner.model_inputs())[0]
    inner_cfg = inner.copy({
        "feed_dict": {feed_name: "image"},
        "fetch_dict": {"features": feat.get("feature_output")},
        "transpose_dict": {feed_name: [0, 3, 1, 2]},
        "normalize_dict": {feed_name: {"scale": float(feat.get("scale"))}}})
    feat_feeds = {feed_name: imgs[:min(128, n_rows)]}
    dev = _device_resident_rate(inner_cfg, feat_feeds)
    dev_fused = _fused_or_none(inner_cfg, feat_feeds)
    print(json.dumps({"metric": "image_featurizer_images_per_sec",
                      **res, "unit": "images/sec/chip",
                      "device_resident_ips": dev,
                      "device_resident_ips_fused": dev_fused,
                      "platform": _platform()}), flush=True)


def bench_shap():
    """Config #5: KernelSHAP over an ONNXModel — stresses repeated batched
    inference (the explainer hot path, KernelSHAPBase.scala:43-94)."""
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.explainers.shap import VectorSHAP
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.onnx import builder as O

    d = 8
    rng = np.random.default_rng(0)
    w1 = rng.normal(0, 0.5, (d, 32)).astype(np.float32)
    w2 = rng.normal(0, 0.5, (32, 2)).astype(np.float32)
    g = O.make_graph(
        [O.make_node("MatMul", ["x", "w1"], ["h"]),
         O.make_node("Relu", ["h"], ["r"]),
         O.make_node("MatMul", ["r", "w2"], ["logits"]),
         O.make_node("Softmax", ["logits"], ["probs"], axis=-1)],
        "mlp",
        inputs=[O.make_tensor_value_info("x", np.float32, ["N", d])],
        outputs=[O.make_tensor_value_info("probs", np.float32, ["N", 2])],
        initializers={"w1": w1, "w2": w2})
    # one jitted dispatch scores THOUSANDS of coalition rows: the explainer
    # already batches all rows x samples through one _score_frame pass, so
    # the inner batch size should match that scale — 256-row batches made
    # the leg dispatch-count-bound (32 tiny dispatches per explain pass)
    m_samples = 8 if SMALL else 128
    n_rows = 4 if SMALL else 64
    n_rows = int(os.environ.get("BENCH_SHAP_ROWS", n_rows))
    inner = ONNXModel(O.make_model(g), feed_dict={"x": "features"},
                      fetch_dict={"probs": "probs"},
                      mini_batch_size=max(256, n_rows * m_samples),
                      pin_devices=False)
    X = rng.normal(0, 1, (n_rows, d)).astype(np.float32)
    bg = rng.normal(0, 1, (16, d)).astype(np.float32)
    shap = VectorSHAP(model=inner, input_col="features",
                      target_col="probs", target_classes=[1],
                      num_samples=m_samples,
                      background_data=DataFrame(
                          {"features": [b for b in bg]}))
    df = DataFrame({"features": [x for x in X]})
    res = _bench_transform(shap, df, n_rows)
    # device-resident: the coalition-scoring dispatch on a pre-staged
    # (n*m, d) matrix, divided back to explained-rows/sec
    flat = rng.normal(0, 1, (n_rows * m_samples, d)).astype(np.float32)
    dev_score = _device_resident_rate(inner, {"x": flat})
    dev_score_fused = _fused_or_none(inner, {"x": flat})
    print(json.dumps({"metric": "kernel_shap_rows_per_sec",
                      **res,
                      "unit": "explained rows/sec/chip",
                      "device_resident_rows_per_sec":
                          round(dev_score / m_samples, 2),
                      "device_resident_rows_per_sec_fused":
                          (round(dev_score_fused / m_samples, 2)
                           if dev_score_fused is not None else None),
                      "samples_per_row": m_samples,
                      "platform": _platform()}), flush=True)


def _platform():
    import jax
    return jax.default_backend()


ALL = {"bert": bench_bert, "featurizer": bench_featurizer,
       "shap": bench_shap}


def main():
    targets = sys.argv[1:] or list(ALL)
    for t in targets:
        ALL[t]()


if __name__ == "__main__":
    main()
