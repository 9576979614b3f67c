"""Serving latency bench — the reference's only serving perf claim is
"sub-millisecond latency" for continuous Spark Serving
(``website/docs/features/spark_serving/about.md:18,150-153``); this measures
the same request→pipeline→reply loop here with hard numbers.

Two configs, one JSON line each:

* ``echo``   — trivial transform (adds a constant column): pure serving-stack
  latency (HTTP parse, queue, batch, route, reply), the reference's claim.
* ``model``  — a jitted linear scorer in the loop: what a real pipeline adds.

Latency is measured client-side over sequential keep-alive requests
(p50/p99), plus a concurrent-burst throughput figure from 8 threads.
The load curve is driven by ``scripts/serving_client.py`` — an open-loop
rate-controlled generator in a SEPARATE process that flags its own
saturation, so curve points are honest about when they stop measuring the
server (round-3 weakness: co-located thread bursts measured the client).

Default CPU-only (the serving stack is host code; run anywhere).
``BENCH_SERVING_TPU=1`` additionally serves a real ONNX model on the
default (TPU) backend through the batching dispatcher — the chip-in-the-
loop row, where every request pays the host↔device round trip.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TPU_MODE = os.environ.get("BENCH_SERVING_TPU", "0") == "1"

if not TPU_MODE:
    # serving latency is host-side by definition; without this the jitted
    # scorer lands on the chip and every request pays a host round trip
    from mmlspark_tpu.utils.device import force_cpu  # noqa: E402
    force_cpu()


def _post(url: str, body: bytes) -> bytes:
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.read()


def _measure(url: str, payload: dict, n: int, warmup: int = 20):
    body = json.dumps(payload).encode()
    for _ in range(warmup):
        _post(url, body)
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        _post(url, body)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat = np.sort(np.array(lat))
    return (round(float(np.percentile(lat, 50)), 3),
            round(float(np.percentile(lat, 99)), 3))


def _burst(url: str, payload: dict, threads: int = 8, per_thread: int = 50):
    """Aggregate req/s over a thread burst on PERSISTENT keep-alive
    connections (one per worker — a fresh TCP connection per request would
    measure ThreadingHTTPServer's thread-spawn path, not the serving loop).
    Failed requests are counted and excluded from the rate so an overloaded
    run reads as degraded, not as a crash or an inflated number."""
    import http.client
    from urllib.parse import urlparse
    u = urlparse(url)
    body = json.dumps(payload).encode()
    ok, errs = [0], [0]
    lock = threading.Lock()

    def worker():
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
        o = e = 0
        for _ in range(per_thread):
            try:
                conn.request("POST", u.path or "/", body,
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                r.read()
                o += 1
            except Exception:
                e += 1
                conn.close()    # reconnect after an error
        conn.close()
        with lock:
            ok[0] += o
            errs[0] += e

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    return round(ok[0] / dt, 1), errs[0]


def _driven(url, rate, duration, conns, payload):
    """One rate-controlled curve point from the separate-process client."""
    import subprocess
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "serving_client.py"),
         url, str(rate), str(duration), str(conns)],
        input=json.dumps(payload).encode(),
        capture_output=True, timeout=duration * 4 + 60)
    if r.returncode != 0:
        raise RuntimeError(r.stderr.decode()[-500:])
    return json.loads(r.stdout)


def main():
    from mmlspark_tpu.serving.engine import ServingEngine

    n = int(os.environ.get("BENCH_SERVING_N", "300"))

    # --- echo: serving-stack floor --------------------------------------
    def echo(df):
        out = df.with_column("reply", [{"ok": True, "x": float(x)}
                                       for x in df["x"]])
        return out

    with ServingEngine(echo, schema={"x": float}, poll_timeout=0.001) as eng:
        url = eng.address
        p50, p99 = _measure(url, {"x": 1.5}, n)
        rps, _ = _burst(url, {"x": 1.5})
    print(json.dumps({"metric": "serving_echo_latency_ms", "p50": p50,
                      "p99": p99, "burst_rps_8threads": rps,
                      "n": n}), flush=True)

    if TPU_MODE:
        # chip-in-the-loop ONLY: the host-side scorer rows below would land
        # their jax.jit on the chip (a host round trip per request) and
        # corrupt the host-serving curve — those rows are produced by the
        # default CPU-pinned run
        _tpu_section(ServingEngine, n)
        return

    # --- model: jitted scorer in the loop -------------------------------
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(np.random.default_rng(0).normal(0, 1, (16,)), jnp.float32)
    score = jax.jit(lambda X: jnp.tanh(X @ w))

    def model(df):
        X = jnp.asarray(np.stack([np.asarray(v, np.float32)
                                  for v in df["features"]]))
        y = np.asarray(score(X))
        return df.with_column("reply", [{"score": float(s)} for s in y])

    feats = [0.1] * 16
    with ServingEngine(model, schema={"features": list},
                       poll_timeout=0.001) as eng:
        url = eng.address
        _post(url, json.dumps({"features": feats}).encode())  # compile
        p50, p99 = _measure(url, {"features": feats}, n)
        rps, _ = _burst(url, {"features": feats})
    print(json.dumps({"metric": "serving_model_latency_ms", "p50": p50,
                      "p99": p99, "burst_rps_8threads": rps,
                      "n": n}), flush=True)

    # --- load curve: rate-controlled clients in a SEPARATE process -------
    # For each transport × dispatcher count, step the offered rate up until
    # the server degrades (errors / p99 blow-up) or the CLIENT saturates —
    # and report which of the two stopped the sweep. The client process
    # flags its own saturation, so a curve point never silently
    # under-reports the server (round-3 weakness #8).
    ncpu = os.cpu_count() or 1
    duration = float(os.environ.get("BENCH_SERVING_DURATION", "3"))
    conns = int(os.environ.get("BENCH_SERVING_CONNS", "16"))
    for transport in ("threaded", "async"):
        for nd in (1, 2, 4):
            with ServingEngine(model, schema={"features": list},
                               poll_timeout=0.001, n_dispatchers=nd,
                               transport=transport) as eng:
                url = eng.address
                _post(url, json.dumps({"features": feats}).encode())
                best, first_bad, why = None, None, None
                rate = 100.0
                while rate <= 12800:
                    pt = _driven(url, rate, duration, conns,
                                 {"features": feats})
                    if pt["errors"] or pt.get("p99_ms", 0) > 250:
                        first_bad, why = pt, "server"
                        break
                    if pt["client_saturated"]:
                        first_bad, why = pt, "client"
                        break
                    best = pt
                    rate *= 2
            print(json.dumps({
                "metric": "serving_rate_curve",
                "transport": transport, "dispatchers": nd,
                "host_cpus": ncpu, "connections": conns,
                "max_clean_point": best,
                "limited_by": why or "sweep_ceiling",
                "first_degraded_point": first_bad}), flush=True)

    # (chip-in-the-loop section runs in TPU_MODE via the early return above)


def _tpu_section(ServingEngine, n):
    """Chip in the loop: request → batching dispatcher → ONNXModel on the
    default (TPU) backend → reply. Reference claim anchor:
    HTTPSourceV2.scala:476-697 + ONNXModel. Every request pays
    host→device→host; the batching dispatcher amortizes it across the
    requests it drains together."""
    import jax

    from mmlspark_tpu.core import DataFrame as MDF
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.zoo.resnet import (ResNetConfig,
                                                export_resnet_onnx)

    duration = float(os.environ.get("BENCH_SERVING_DURATION", "3"))
    plat = jax.devices()[0].platform
    # a ResNet-18-ish backbone at 64px: a real conv model, small
    # enough that serving latency is not dominated by one forward
    cfg = ResNetConfig([2, 2, 2, 2], num_classes=100, width=32)
    m = ONNXModel(export_resnet_onnx(cfg, seed=0),
                  feed_dict={"input": "image"},
                  fetch_dict={"logits": "logits"},
                  argmax_dict={"pred": "logits"},
                  transpose_dict={"input": [0, 3, 1, 2]},
                  mini_batch_size=64, compute_dtype="bfloat16")

    def tpu_model(df):
        k = len(df["image"])
        col = np.empty(k, dtype=object)
        for i, v in enumerate(df["image"]):
            col[i] = np.asarray(v, np.uint8).reshape(64, 64, 3)
        out = m.transform(MDF({"image": col}))
        return df.with_column(
            "reply", [{"pred": int(p)} for p in out["pred"]])

    img = np.random.default_rng(0).integers(
        0, 256, (64, 64, 3), np.uint8).reshape(-1).tolist()
    # warm every jit bucket the driven phase can hit: concurrent requests
    # drain as ragged groups padded to pow2 buckets (1/2/4/8) and each
    # unseen bucket is a fresh REMOTE compile — the r5 campaign's rate
    # point (0.3 achieved rps, 7 errors at target 32) was those compiles
    # landing inside the 3 s window, not serving capacity. Compile
    # directly through the model (an HTTP-side warmup would time out
    # while a remote compile runs); same discipline as bench_decode's
    # full-pool warmup.
    arr = np.asarray(img, np.uint8).reshape(64, 64, 3)
    for k in (1, 2, 4, 8):
        col = np.empty(k, dtype=object)
        col[:] = [arr] * k
        m.transform(MDF({"image": col}))
    with ServingEngine(tpu_model, schema={"image": list},
                       poll_timeout=0.001, n_dispatchers=2,
                       transport="async") as eng:
        url = eng.address
        _post(url, json.dumps({"image": img}).encode())   # engine-path warm
        _burst(url, {"image": img}, threads=8, per_thread=2)
        p50, p99 = _measure(url, {"image": img}, max(n // 4, 40))
        pt = _driven(url, 32.0, duration, 8, {"image": img})
    print(json.dumps({"metric": "serving_onnx_model_latency_ms",
                      "platform": plat, "p50": p50, "p99": p99,
                      "rate_point": pt}), flush=True)


if __name__ == "__main__":
    main()
