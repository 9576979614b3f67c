"""Headline benchmark: ResNet-50 ONNX inference through DataFrame.transform.

Mirrors BASELINE.json config #1 — the reference runs a ResNet-class ONNX
model through ``ONNXModel.transform`` on onnxruntime (CUDA EP on GPU, CPU EP
in the quickstart). Here the same user-visible pipeline (DataFrame →
minibatch → ONNX graph → output column) executes as an XLA-compiled program
on the local TPU chip. Prints ONE JSON line with images/sec/chip;
``vs_baseline`` is against the 3000 img/s/chip north-star target. Extra keys:
``platform``/``device`` (what actually ran) and ``mfu`` (model FLOPs
utilization, FLOPs taken from XLA cost analysis, peak from the device kind).

The bench needs a chip: it initialises JAX once, in this one process, and
exits non-zero when the device JAX finds is not a TPU. A CPU run is never
reported under a device metric's name.
"""

import contextlib
import json
import os
import signal
import sys
import threading
import time

import numpy as np

TARGET_IMG_PER_SEC = 3000.0

#: internal wall-clock budget (seconds): the bench must emit its one JSON
#: line before any external `timeout` kills it (campaign logs show rc=124
#: with an empty tail when the timed section overran). A watchdog thread
#: emits whatever has been measured so far and exits 0 at the deadline.
DEFAULT_WALL_BUDGET_S = 540.0


def _partial_path():
    """Where per-phase checkpoints land. ``BENCH_PARTIAL_PATH`` overrides;
    empty string disables; default sits next to this file so the driver
    finds it with the BENCH_r0*.json trajectory."""
    p = os.environ.get("BENCH_PARTIAL_PATH")
    if p is None:
        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_partial.json")
    return p or None


class _OneShotReport:
    """The bench's single JSON line, emittable exactly once from any thread.

    The main path fills ``record`` in place as results land and emits at the
    end; the budget watchdog emits the partial record at the deadline. The
    lock guarantees the driver never sees zero or two lines.

    ``checkpoint`` additionally persists the record-so-far to
    ``_partial_path()`` after every completed phase (tmp + atomic rename):
    the SIGTERM handlers cannot outrun ``timeout -k``'s follow-up SIGKILL
    (BENCH_r05.json: rc=124, empty tail, every completed phase lost), but
    a file already on disk survives any kill.
    """

    def __init__(self, record: dict, path=None):
        self.record = record
        self.path = path
        self._phases = []
        self._lock = threading.Lock()
        self._emitted = False

    def _write_file(self, payload: str) -> None:
        if not self.path:
            return
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
            os.replace(tmp, self.path)  # atomic: never a torn partial
        except OSError:
            pass                        # checkpointing must never kill a run

    def checkpoint(self, phase: str) -> None:
        """Persist the record after ``phase`` completed (atomic rename)."""
        with self._lock:
            if self._emitted:
                return
            self._phases.append(phase)
            snap = dict(self.record)
            snap["partial"] = {"complete": False,
                               "phases_done": list(self._phases)}
            payload = json.dumps(snap, default=str)
        self._write_file(payload)

    def emit(self) -> bool:
        with self._lock:
            if self._emitted:
                return False
            self._emitted = True
            self.record["partial"] = {"complete": True,
                                      "phases_done": list(self._phases)}
        payload = json.dumps(self.record, default=str)
        sys.stdout.write(payload + "\n")
        sys.stdout.flush()
        self._write_file(payload)
        return True

class _PhaseTimeout(BaseException):
    """Raised in the main thread by the SIGALRM phase guard. Inherits
    BaseException so the per-pass ``except Exception`` blocks cannot
    swallow it and mislabel a phase deadline as a pass failure."""


def _bench_costs(harvest=False):
    """Cost-attribution sub-record from the process-global CostLedger:
    per-class resource totals (device-seconds, transfer bytes, KV page
    holds), the heavy-hitter table size and its top entry, and — on the
    emit paths — how many rows landed in the tuning ObservationStore.
    Refreshed on EVERY exit path, including the atomic per-phase partial
    checkpoints, so a SIGKILLed run still reports where its device time
    went (docs/observability.md, "Cost attribution")."""
    try:
        from mmlspark_tpu.observability.ledger import get_ledger
        snap = get_ledger().snapshot()
        out = {"classes": snap["classes"],
               "weights": snap["weights"],
               "top_k": snap["top_k"],
               "heavy_hitters": len(snap["heavy_hitters"])}
        if snap["heavy_hitters"]:
            out["top_hitter"] = snap["heavy_hitters"][0]
        if harvest:
            from mmlspark_tpu.tuning.observations import harvest_costs
            out["harvested_observations"] = harvest_costs(snap)
        return out
    except Exception:                   # noqa: BLE001
        return None


_MULTI_MODEL_DRILL: dict = {}


def _multi_model_drill() -> dict:
    """Deterministic in-process drill of the multi-model traffic plane
    (docs/guide.md, "Multi-model serving and tenant fairness"): no
    sockets, no sleeps — measures the three headline properties directly
    against the primitives the worker server composes."""
    import types as _types

    from mmlspark_tpu.observability import get_tracker
    from mmlspark_tpu.serving.admission import (AdmissionQueue,
                                                ConsistentHashRing)
    from mmlspark_tpu.serving.registry import ModelRegistry

    # (a) weighted-fair goodput shares under standing backlog: with
    # weights 3/2/1 the first 24 DRR dequeues must split 12/8/4
    weights = {"acme": 3.0, "beta": 2.0, "gamma": 1.0}
    q = AdmissionQueue(weight_fn=lambda t: weights.get(t, 1.0))
    for _ in range(12):
        for t in weights:
            q.put_nowait(_types.SimpleNamespace(tenant=t))
    drained = [q.get_nowait().tenant for _ in range(24)]
    shares = {t: round(drained.count(t) / 24, 4) for t in weights}

    # (b) prefix-affinity retention across one membership change: the
    # ring moves ~1/n of the keyspace where hash(key) % n moves ~(n-1)/n
    ring = ConsistentHashRing()
    ring.rebuild(["w0", "w1", "w2"])
    keys = [f"prefix-{i:03d}" for i in range(200)]
    before = {k: ring.route(k) for k in keys}
    ring.rebuild(["w0", "w1", "w2", "w3"])
    kept = sum(before[k] == ring.route(k) for k in keys)
    hit_rate = round(kept / len(keys), 4)

    # (c) canary auto-rollback: a local registry (the process-global one
    # stays untouched) with a breaching canary window must roll back
    reg = ModelRegistry(min_requests=5, check_every=1)
    reg.load("bench-canary", "v1", handle=lambda df: df)
    reg.load("bench-canary", "v2", handle=lambda df: df, canary_percent=50)
    tracker = get_tracker()
    for _ in range(8):
        tracker.observe(transport="bench", route="api",
                        model="bench-canary@v1", seconds=0.01, error=False)
        tracker.observe(transport="bench", route="api",
                        model="bench-canary@v2", seconds=0.01, error=True)
    verdicts = reg.check_canaries()
    rollbacks = sum(1 for v in verdicts if v.get("breach"))
    states = {v.label: v.state for v in reg.versions("bench-canary")}
    reg.reset()
    return {"goodput_shares": shares,
            "goodput_shares_expected": {"acme": 0.5, "beta": round(1 / 3, 4),
                                        "gamma": round(1 / 6, 4)},
            "ring_hit_rate_after_member_join": hit_rate,
            "canary_rollbacks": rollbacks,
            "canary_states_after_drill": states}


def _bench_multi_model():
    """Multi-model traffic-plane sub-record: the cached one-shot drill
    above plus the live registry/WFQ/ring counters, re-read on EVERY
    exit path (like the cost sub-record) so partial checkpoints still
    carry the traffic plane's state."""
    try:
        if not _MULTI_MODEL_DRILL:
            _MULTI_MODEL_DRILL.update(_multi_model_drill())
        out: dict = {"drill": dict(_MULTI_MODEL_DRILL)}
    except Exception as e:              # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"[:200]}
    try:
        from mmlspark_tpu.observability import snapshot
        snap = snapshot()

        def _series(name):
            return (snap.get(name) or {}).get("series") or []

        def _total(name):
            return sum(s.get("value", 0) for s in _series(name))

        deq = {s["labels"].get("tenant", "?"): s["value"]
               for s in _series("mmlspark_wfq_dequeued_total")}
        total_deq = sum(deq.values())
        routes = {s["labels"].get("outcome", "?"): s["value"]
                  for s in _series("mmlspark_ring_routes_total")}
        total_routes = sum(routes.values())
        out["counters"] = {
            "wfq_dequeued": total_deq,
            "wfq_goodput_shares": (
                {t: round(v / total_deq, 4) for t, v in sorted(deq.items())}
                if total_deq else {}),
            "wfq_shed": _total("mmlspark_wfq_shed_total"),
            "canary_rollbacks": _total("mmlspark_registry_rollbacks_total"),
            "ring_rebuilds": _total("mmlspark_ring_rebuilds_total"),
            "ring_affine_route_rate": (
                round(routes.get("affine", 0) / total_routes, 4)
                if total_routes else None),
        }
    except Exception:                   # noqa: BLE001
        pass
    return out


@contextlib.contextmanager
def _phase_guard(record: dict, name: str, seconds: float, report=None):
    """Per-phase wall-clock guard: arm SIGALRM so a stuck phase raises in
    the MAIN thread at its deadline and is skipped (named in the record)
    instead of dragging the whole bench into the external timeout — the
    BENCH_r05 failure mode was one overrunning section eating every later
    phase AND the JSON emit. No-ops off the main thread (signals only
    deliver there) and for non-positive budgets. When ``report`` is given,
    the record-so-far is checkpointed to disk as the phase ends — timed
    out or not — so a later SIGKILL cannot erase it."""
    def _observe_phase(elapsed: float, timed_out: bool) -> None:
        # per-phase SLO sample: bench phases land in the same scorecard
        # machinery the serving plane uses (transport="bench", route=phase),
        # so the emitted record's "slo" block carries phase p99s/timeouts
        try:
            from mmlspark_tpu.observability import get_tracker
            get_tracker().observe(transport="bench", route=name,
                                  seconds=elapsed, error=timed_out)
        except Exception:               # noqa: BLE001
            pass
        # keep the checkpoint's cost attribution as fresh as its phases
        # (harvest only on the emit paths — not once per checkpoint)
        record["costs"] = _bench_costs()
        record["multi_model"] = _bench_multi_model()

    if (seconds <= 0
            or threading.current_thread() is not threading.main_thread()):
        t0 = time.perf_counter()
        yield
        _observe_phase(time.perf_counter() - t0, False)
        if report is not None:
            report.checkpoint(name)
        return

    def _on_alarm(signum, frame):
        raise _PhaseTimeout(name)

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(max(1, int(seconds)))
    t0 = time.perf_counter()
    timed_out = False
    try:
        yield
    except _PhaseTimeout:
        timed_out = True
        record.setdefault("phase_timeouts", []).append(name)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
        _observe_phase(time.perf_counter() - t0, timed_out)
        if report is not None:
            report.checkpoint(name)


def _install_signal_handlers(report: "_OneShotReport", fill_partial):
    """SIGTERM/SIGALRM → emit the partial record, then exit 0.

    An external ``timeout`` sends SIGTERM before SIGKILL; without this the
    run's completed phases are lost (campaign log BENCH_r05.json: rc=124,
    empty tail). SIGALRM lands here only when no phase guard is armed —
    same response. ``fill_partial`` folds the counters measured so far
    into the record before the emit."""
    def _on_signal(signum, frame):
        name = signal.Signals(signum).name
        report.record["signal"] = name
        report.record.setdefault(
            "midrun_error",
            f"killed by {name}; partial record with completed phases")
        try:
            fill_partial()
        except Exception:               # noqa: BLE001
            pass
        report.emit()
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGALRM, _on_signal)


# peak bf16 FLOP/s of one chip, keyed by the generation parsed from
# ``device_kind``. v5e ("TPU v5 lite"): 197 TFLOP/s bf16 — Google Cloud
# documentation, "TPU v5e" (393 TOP/s there is the int8 figure); the other
# rows are the same documentation's per-chip bf16 peaks.
PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5": 197e12,      # v5e / "TPU v5 lite"
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops(device_kind: str) -> float:
    """Published bf16 peak of one chip of ``device_kind``. A kind the table
    does not know is an error, never a default."""
    from mmlspark_tpu.utils.device import generation_from_kind
    gen = generation_from_kind(device_kind)
    if gen not in PEAK_FLOPS:
        raise KeyError(f"device_kind {device_kind!r} is not in bench.py's "
                       f"peak table (known: {sorted(PEAK_FLOPS)})")
    return PEAK_FLOPS[gen]


def _init_backend():
    """(platform, device_kind) of the one device this process runs on.
    Exits non-zero unless it is a TPU the peak table knows."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"bench.py: needs a TPU; JAX found platform {d.platform!r} "
              f"({d.device_kind}). `python chip_smoke.py --small` rehearses "
              "on the CPU.", file=sys.stderr)
        raise SystemExit(2)
    peak_flops(d.device_kind)
    return d.platform, d.device_kind


def _generation_phase(on_tpu: bool) -> dict:
    """Continuous-decoding throughput through the paged-KV engine.

    Mixed prompt lengths (short, medium, and one longer than the prefill
    chunk budget) plus a shared-prefix cohort drive the whole scheduler:
    chunked prefill interleaves with decode ticks, prefix pages are CoW-
    shared, and the autotuner walks gamma/chunk from live occupancy and
    acceptance. Reports tok/s (the >4,265 target on real TPU hardware),
    p50/p99 decode-step latency, the prefix-page share rate, and the
    gamma trajectory — the numbers ROADMAP item 3 exists to move."""
    from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                     init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    if on_tpu:
        cfg = TransformerConfig(vocab=8192, d_model=512, heads=8,
                                layers=8, d_ff=2048, max_len=1024,
                                causal=True)
        d_cfg = TransformerConfig(vocab=8192, d_model=128, heads=4,
                                  layers=2, d_ff=512, max_len=1024,
                                  causal=True)
        slots, max_new, chunk, n_reqs = 16, 64, 256, 48
        lens = (24, 96, 384)
    else:
        # tiny deterministic config: the phase must finish in seconds on
        # the CPU fallback — the POINT there is exercising the scheduler
        # end-to-end, not the absolute number
        cfg = TransformerConfig(vocab=211, d_model=64, heads=4,
                                layers=2, d_ff=128, max_len=192,
                                causal=True)
        d_cfg = TransformerConfig(vocab=211, d_model=32, heads=2,
                                  layers=1, d_ff=64, max_len=192,
                                  causal=True)
        slots, max_new, chunk, n_reqs = 4, 12, 32, 10
        lens = (6, 20, 48)
    params = init_transformer(cfg, 0)
    d_params = init_transformer(d_cfg, 1)
    eng = ContinuousDecoder(params, cfg, max_slots=slots,
                            max_len=cfg.max_len, draft_params=d_params,
                            draft_cfg=d_cfg, gamma=2,
                            page_size=16, prefill_chunk=chunk,
                            autotune=True)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab, lens[1], dtype=np.int32)

    def _drain():
        while any(r is not None for r in eng._slot_req) or eng._waiting:
            eng.step()

    # warm every program shape OUTSIDE the timed section (one request per
    # prompt-length bucket, incl. a chunked one and a prefix pair)
    warm = [eng.submit(rng.integers(1, cfg.vocab, n, dtype=np.int32),
                       max_new_tokens=4) for n in lens]
    warm.append(eng.submit(sys_prompt, max_new_tokens=4,
                           prefix_key="bench-sys"))
    warm.append(eng.submit(
        np.concatenate([sys_prompt,
                        rng.integers(1, cfg.vocab, 4, dtype=np.int32)]),
        max_new_tokens=4, prefix_key="bench-sys"))
    _drain()
    # NOTE: an autotuner gamma change mid-run compiles that gamma's tick
    # once; on a cold compile cache that lands in the latency tail (the
    # max, usually the p99 too on short runs). decode_step_p50_ms is the
    # steady-state number; the trajectory fields say when gamma moved.
    share_before = eng._kv.stats["prefix_share_hits"]

    reqs = []
    for i in range(n_reqs):
        if i % 3 == 2:          # shared-prefix cohort
            ids = np.concatenate([
                sys_prompt, rng.integers(1, cfg.vocab, 4, dtype=np.int32)])
            reqs.append(eng.submit(ids, max_new_tokens=max_new,
                                   prefix_key="bench-sys"))
        else:
            n = lens[i % 2] if i % 6 else lens[2]   # every 6th is chunked
            reqs.append(eng.submit(
                rng.integers(1, cfg.vocab, n, dtype=np.int32),
                max_new_tokens=max_new))
    step_s = []
    t0 = time.perf_counter()
    # one watch over the whole decode loop, heartbeat per engine tick: the
    # stall budget bounds ONE step, so a hung device call mid-generation
    # produces a diagnostic bundle instead of a silent external timeout
    from mmlspark_tpu.observability import watch as _wd_watch
    from mmlspark_tpu.observability.timeseries import get_store as _ts_store
    _history = _ts_store()
    with _wd_watch("bench_generation") as _w:
        while any(r is not None for r in eng._slot_req) or eng._waiting:
            s0 = time.perf_counter()
            eng.step()
            _w.beat()
            step = time.perf_counter() - s0
            step_s.append(step)
            # per-tick history: the embedded timeline shows step latency
            # over the run (warmup spike, steady state), not just the
            # batch quantiles below
            _history.record("bench_decode_step_ms", step * 1e3)
    elapsed = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    lat = np.sort(np.asarray(step_s))
    pool = eng._kv
    shared = pool.stats["prefix_share_hits"] - share_before
    n_prefix = sum(1 for i in range(n_reqs) if i % 3 == 2)
    out = {
        "tok_per_sec": round(toks / elapsed, 2),
        "mesh_shape": "single",
        # send-wait-send latency regime: never compare these quantiles
        # with the scenarios phase's open-loop (CO-corrected) numbers
        "loop_mode": "closed",
        "tokens": toks, "requests": n_reqs, "wall_s": round(elapsed, 3),
        "decode_step_p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 3),
        "decode_step_p99_ms": round(
            float(lat[min(len(lat) - 1, int(len(lat) * 0.99))]) * 1e3, 3),
        "decode_step_max_ms": round(float(lat[-1]) * 1e3, 3),
        "steps": len(step_s),
        "prefix_share_hits": int(shared),
        # pages a prefix-cohort request reused instead of recomputing,
        # per request — the CoW payoff the pool exists for
        "prefix_pages_shared_per_hit": (
            round(shared / n_prefix, 2) if n_prefix else None),
        "kvpool": {"pages_total": pool.num_pages - 1,
                   "high_water": pool.high_water,
                   "defrag_moves": pool.stats["defrag_moves"],
                   "prefill_chunks": pool.stats["prefill_chunks"]},
        # which paged-attention impl decoded, and what the kernel saved:
        # the gather fallback materializes a contiguous K/V copy per paged
        # call — hbm_bytes_saved_per_step is that per-engine-tick traffic
        # the Pallas kernel never moves (0 when gather actually ran,
        # since nothing was saved)
        "paged_attn": {
            "impl": eng._attn_impl,
            "kv_dtype": eng._kv_dtype,
            "ticks_kernel": pool.stats.get("attn_ticks_kernel", 0),
            "ticks_gather": pool.stats.get("attn_ticks_gather", 0),
            "gather_bytes_total": pool.stats.get("gather_bytes", 0),
            "hbm_bytes_saved_per_step": (
                eng._k * eng._gather_bytes_tick
                if eng._attn_impl == "kernel" else 0)},
        "gamma_trajectory": [h for h in (eng._tuner.history
                                         if eng._tuner else [])
                             if h["knob"] == "gamma"],
        "chunk_trajectory": [h for h in (eng._tuner.history
                                         if eng._tuner else [])
                             if h["knob"] == "chunk"],
        "engine_stats": dict(eng.stats),
        # time-resolved view of the same run: per-bucket min/max/mean of
        # the step latency series recorded in the loop above, so a spike
        # mid-run is visible even though the quantiles flatten it
        "timeseries": _history.snapshot(max(elapsed + 5.0, 30.0),
                                        names=["bench_decode_step_ms"]),
    }
    out["quantized"] = _quantized_generation_pass(cfg, params)
    return out


def _quantized_generation_pass(cfg, params) -> dict:
    """One int8-KV pass through the same engine: the quantized data plane's
    realized savings, counter-asserted from the pool's own byte accounting.

    ``hbm_bytes_saved_per_step`` is what a decode tick stopped reading from
    HBM versus the bf16 layout at identical geometry (the >=1.9x acceptance
    number at hd=64); ``contexts_held_at_budget`` is how many max_len
    contexts the SAME page-budget bytes now hold. ``kv_quant_error_*`` is
    the dequant-oracle relative RMS the SLO canary watches."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.kv_quant import kv_bytes_per_position
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    eng = ContinuousDecoder(params, cfg, max_slots=4, max_len=min(
        cfg.max_len, 96), page_size=16, kv_dtype="int8", quant_probe=1)
    rng = np.random.default_rng(7)
    reqs = [eng.submit(rng.integers(1, cfg.vocab, 6 + 5 * i,
                                    dtype=np.int32), max_new_tokens=8)
            for i in range(4)]
    t0 = time.perf_counter()
    steps = 0
    while any(r is not None for r in eng._slot_req) or eng._waiting:
        eng.step()
        steps += 1
    elapsed = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    pool = eng._kv
    hd = cfg.d_model // cfg.heads
    bf16_pos = cfg.layers * kv_bytes_per_position(
        cfg.heads, hd, jnp.bfloat16, False)
    quant_pos = pool.bytes_per_position()
    bf16_tick = eng._S * eng._Lc * bf16_pos
    stats = pool.stats
    probes = stats["quant_error_probes"]
    return {
        "kv_dtype": eng._kv_dtype,
        "tok_per_sec": round(toks / elapsed, 2) if elapsed > 0 else None,
        "tokens": toks, "steps": steps,
        "kv_bytes_per_position": quant_pos,
        "kv_bytes_per_position_bf16": bf16_pos,
        "hbm_bytes_per_tick": eng._gather_bytes_tick,
        "hbm_bytes_saved_per_step": bf16_tick - eng._gather_bytes_tick,
        "hbm_bytes_ratio_vs_bf16": round(bf16_pos / quant_pos, 4),
        "bytes_per_token": round(
            steps * eng._gather_bytes_tick / max(1, toks), 1),
        # fixed byte budget = the bf16 pool's device footprint; the
        # quantized layout packs this many more max_len contexts in it
        "contexts_held_at_budget": {
            "budget_bytes": pool.num_pages * eng._page * bf16_pos,
            "bf16": pool.num_pages * eng._page * bf16_pos
            // max(1, eng._L * bf16_pos),
            "quantized": pool.num_pages * eng._page * bf16_pos
            // max(1, eng._L * quant_pos)},
        "kv_quant_error_probes": probes,
        "kv_quant_error_mean": (
            round(stats["quant_error_sum"] / probes, 6) if probes else None),
        "kv_quant_error_max": (
            round(stats["quant_error_max"], 6) if probes else None),
    }


def _failover_phase() -> dict:
    """Session-failover sub-record: checkpoint a live mid-decode session
    on engine A, restore it on engine B both cold (journal-style
    re-prefill of prompt+emitted) and warm (KV page-blob adoption), and
    time each handoff. ``*_parity`` must be True — both paths are
    token-identical to the uninterrupted run by construction; the numbers
    this phase exists for are ``warm_adopt_ms`` vs ``cold_restore_ms``
    (what a graceful drain saves over a kill) and ``blob_bytes`` (what
    the warm path costs on the wire)."""
    from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                     init_transformer)
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    cfg = TransformerConfig(vocab=128, d_model=64, heads=4, layers=2,
                            d_ff=128, max_len=64, causal=True)
    params = init_transformer(cfg, 0)
    prompt = np.arange(5, 13, dtype=np.int32)
    max_new = 16

    def _drain(eng, req):
        while not req.done:
            eng.step()
        return eng.session_result(req)

    base = ContinuousDecoder(params, cfg, max_slots=2, max_len=64,
                             page_size=8)
    want = _drain(base, base.submit(prompt, max_new))
    src = ContinuousDecoder(params, cfg, max_slots=2, max_len=64,
                            page_size=8)
    live = src.submit(prompt, max_new)
    for _ in range(6):                  # genuinely mid-decode
        src.step()
    ckpt = src.checkpoint_session(live)
    blob_bytes = (sum(len(e[k]) for e in ckpt["kv"]["data"] for k in e)
                  if ckpt["kv"] else 0)
    cold_eng = ContinuousDecoder(params, cfg, max_slots=2, max_len=64,
                                 page_size=8)
    warm_eng = ContinuousDecoder(params, cfg, max_slots=2, max_len=64,
                                 page_size=8)
    # prime both engines' compiled programs so the timings below measure
    # the handoff, not first-touch compilation
    for e in (cold_eng, warm_eng):
        _drain(e, e.submit(prompt, 2))
    t0 = time.perf_counter()
    cold_req = cold_eng.restore_session(ckpt["session"])
    while not cold_req.tokens and not cold_req.done:
        cold_eng.step()                 # includes the re-prefill
    cold_ms = (time.perf_counter() - t0) * 1e3
    cold = cold_eng.session_result(cold_req) if cold_req.done else \
        _drain(cold_eng, cold_req)
    t0 = time.perf_counter()
    warm_req = warm_eng.restore_session(ckpt["session"],
                                        kv_blob=ckpt["kv"])
    while not warm_req.tokens and not warm_req.done:
        warm_eng.step()                 # first token off adopted pages
    warm_ms = (time.perf_counter() - t0) * 1e3
    warm = warm_eng.session_result(warm_req) if warm_req.done else \
        _drain(warm_eng, warm_req)
    return {
        "emitted_at_checkpoint": len(ckpt["session"]["emitted"]),
        "blob_bytes": blob_bytes,
        "cold_restore_ms": round(cold_ms, 3),
        "warm_adopt_ms": round(warm_ms, 3),
        # prefill count past the priming request — 0 proves the warm
        # path re-prefilled nothing
        "warm_reprefills": warm_eng.stats["prefills"] - 1,
        "cold_parity": cold == want,
        "warm_parity": warm == want,
    }


def _multichip_generation_phase(mesh=None) -> dict:
    """Mesh-sharded decode: the same paged-KV engine run once single-chip
    and once shard_map-mounted on ``mesh`` (default: a dp×tp mesh over
    every visible device — dp4×tp2 on 8), with the SAME greedy workload,
    so the record carries tok/s vs chips, scaling efficiency against the
    single-chip rate, and a per-tick collective-time estimate (mesh step
    p50 minus single-chip step p50 — what the ICI adds to a tick). On
    simulated CPU devices the absolute numbers mean nothing; the phase
    exists so real-mesh runs land these fields in the trajectory and so
    the dryrun counter-asserts the kernel actually ran sharded."""
    import jax
    from jax.sharding import Mesh
    from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                     init_transformer)
    from mmlspark_tpu.parallel.mesh import mesh_shape
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    if mesh is None:
        devs = jax.devices()
        n = len(devs)
        tp = 2 if (n % 2 == 0 and n >= 2) else 1
        dp = max(1, n // tp)
        mesh = Mesh(np.array(devs[:dp * tp]).reshape(dp, tp),
                    ("dp", "tp"))
    chips = int(mesh.devices.size)
    # vocab/heads/d_ff all divisible by tp — the Megatron shardings split
    # lm_head on the vocab axis, so the tiny config must tile cleanly
    cfg = TransformerConfig(vocab=256, d_model=64, heads=4, layers=2,
                            d_ff=128, max_len=96, causal=True)
    params = init_transformer(cfg, 0)
    dp = mesh.shape.get("dp", 1)
    slots = max(4, int(dp))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, 6 + (i % 3) * 7, dtype=np.int32)
               for i in range(2 * slots)]

    def _run(m, kv_dtype=None):
        eng = ContinuousDecoder(params, cfg, max_slots=slots, max_len=64,
                                mesh=m, page_size=8, kv_dtype=kv_dtype,
                                quant_probe=1 if kv_dtype else 0)
        warm = [eng.submit(p, max_new_tokens=2) for p in prompts[:3]]
        while any(r is not None for r in eng._slot_req) or eng._waiting:
            eng.step()
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        step_s = []
        t0 = time.perf_counter()
        while any(r is not None for r in eng._slot_req) or eng._waiting:
            s0 = time.perf_counter()
            eng.step()
            step_s.append(time.perf_counter() - s0)
        elapsed = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in reqs)
        p50 = float(np.sort(np.asarray(step_s))[len(step_s) // 2])
        return (toks / elapsed, toks, elapsed, p50,
                [list(r.tokens) for r in reqs], eng)

    tps_1, _, _, p50_1, toks_1, _ = _run(None)
    tps_m, toks, wall, p50_m, toks_m, eng = _run(mesh)
    # one quantized pass through the SAME mesh mount: the sharded int8
    # data plane (scale pools ride P(None, tp, None)) must decode the
    # same workload; token parity vs the quantized single-chip run is
    # the dryrun counter-assert that the sharded dequant kernel ran
    _, _, _, _, toks_q1, _ = _run(None, kv_dtype="int8")
    tps_q, toks_qn, _, _, toks_qm, eng_q = _run(mesh, kv_dtype="int8")
    pool = eng._kv
    return {
        "mesh_shape": mesh_shape(mesh), "chips": chips,
        "tok_per_sec": round(tps_m, 2), "tokens": toks,
        "wall_s": round(wall, 3),
        "tok_per_sec_single_chip": round(tps_1, 2),
        # fixed workload: ideal scaling is chips × the single-chip rate
        "scaling_efficiency": round(tps_m / (tps_1 * chips), 4)
        if tps_1 > 0 else None,
        "collective_ms_per_tick_est": round(
            max(0.0, p50_m - p50_1) * 1e3, 3),
        "token_parity_vs_single_chip": toks_m == toks_1,
        "paged_attn": {
            "impl": eng._attn_impl,
            "kv_dtype": eng._kv_dtype,
            "ticks_kernel": pool.stats.get("attn_ticks_kernel", 0),
            "ticks_gather": pool.stats.get("attn_ticks_gather", 0),
            "gather_bytes_total": pool.stats.get("gather_bytes", 0)},
        "quantized": {
            "kv_dtype": eng_q._kv_dtype,
            "tok_per_sec": round(tps_q, 2), "tokens": toks_qn,
            "hbm_bytes_per_tick": eng_q._gather_bytes_tick,
            # int8 rounding amplifies the tp psum reduction-order ulps,
            # so mesh-vs-single parity is asserted over a short horizon;
            # drift past it is accumulation, not a data-plane bug (the
            # written pages themselves are bit-identical per write)
            "token_parity_horizon": 4,
            "token_parity_vs_single_chip": (
                [t[:4] for t in toks_qm] == [t[:4] for t in toks_q1]),
            "kv_quant_error_probes":
                eng_q._kv.stats["quant_error_probes"],
            "kv_quant_error_last":
                eng_q._kv.stats["quant_error_last"]},
    }


def _scenarios_phase(record: dict) -> dict:
    """Open-loop scenario sub-record (ROADMAP item 5): run the seeded
    ``smoke`` scenario from ``mmlspark_tpu.loadgen`` against a live
    3-worker ServingCluster and report its scorecard — the only latency
    numbers in BENCH measured from *scheduled* send time
    (``loop_mode: "open"``), next to the generation phase's closed-loop
    quantiles. The harvest lands ``slo_scorecard``/``cost_ledger`` rows
    in the ObservationStore, so the tuning phase that follows sees
    traffic-shaped observations from the same run."""
    import threading as _threading

    from mmlspark_tpu.loadgen import (cluster_echo_engine, get_scenario,
                                      run_scenario)
    from mmlspark_tpu.observability.federation import (
        FEDERATION_INTERVAL_ENV)
    from mmlspark_tpu.serving.distributed import ServingCluster

    gen = record.get("generation") or {}
    mesh_shape = str(gen.get("mesh_shape", "single"))
    kv_dtype = (gen.get("paged_attn") or {}).get("kv_dtype")
    prior = os.environ.get(FEDERATION_INTERVAL_ENV)
    os.environ[FEDERATION_INTERVAL_ENV] = "0"   # federate every heartbeat
    cluster = ServingCluster(3, reply_timeout=10.0, max_queue=256)
    stop = _threading.Event()
    engine = cluster_echo_engine(cluster, stop, service_s=0.005, batch=16)
    try:
        card = run_scenario(get_scenario("smoke"), cluster,
                            closed_loop_n=20,
                            mesh_shape=mesh_shape,
                            kv_dtype=kv_dtype)
    finally:
        stop.set()
        engine.join(timeout=2.0)
        cluster.close()
        if prior is None:
            os.environ.pop(FEDERATION_INTERVAL_ENV, None)
        else:
            os.environ[FEDERATION_INTERVAL_ENV] = prior
    # worker-side sampled history (the store outlives cluster.close()):
    # queue pressure over the run, next to the scorecard's own
    # `timeline` sub-record
    from mmlspark_tpu.observability.timeseries import get_store as _ts_store
    card["timeseries"] = _ts_store().snapshot(
        max(float(card.get("window_s") or 0.0) + 10.0, 60.0),
        names=["mmlspark_queue_saturation",
               "mmlspark_queue_drain_rate"])
    return card


def _tuning_phase(record: dict, model, *, batch: int, n_rows: int,
                  ips: float) -> dict:
    """Measurement-driven autotuning sub-record (ROADMAP item 4).

    Folds this run's harvested runner samples together with every prior
    ``BENCH_r0*.json`` into one observation store, fits the cost model, and
    reports (a) the config it would pick for this workload, (b) per-knob
    predicted deltas against the config that actually ran, and (c) a
    regression guard comparing the headline number against the best prior
    round on the same platform — a dip becomes a flagged field in the JSON
    record, not a silent regression in the trajectory.
    """
    import glob

    from mmlspark_tpu.tuning import (CostModel, ObservationStore,
                                     compare_kv_dtype, compare_paged_attn,
                                     get_store, import_bench_records)

    here = os.path.dirname(os.path.abspath(__file__))
    priors = sorted(glob.glob(os.path.join(here, "BENCH_r0*.json")))
    sig = model.tuning_signature()
    store = ObservationStore()          # scratch: this run + the trajectory
    for row in get_store().rows(sig=sig):
        store.record(row)
    imported = import_bench_records(priors, store)
    out = {"imported_bench_records": imported, "store_rows": len(store),
           "sig": sig}
    # this run's generation phase + the imported trajectory, grouped by
    # paged-attention impl: the kernel-vs-gather evidence per placement
    gen = record.get("generation")
    if isinstance(gen, dict) and isinstance(gen.get("tok_per_sec"),
                                            (int, float)):
        from mmlspark_tpu.tuning.observations import _generation_observation
        row = _generation_observation(record, __file__)
        if row is not None:
            store.record(row)
    pa = compare_paged_attn(store)
    if pa:
        out["paged_attn_comparison"] = pa
    kd = compare_kv_dtype(store)
    if kd:
        out["kv_dtype_comparison"] = kd

    histogram = {batch: n_rows // batch}
    if n_rows % batch:
        histogram[n_rows % batch] = 1
    depth0 = int(model.prefetch_depth)
    rows = store.rows(sig=sig)
    if rows:
        cm = CostModel.fit(rows)
        decision = cm.choose(histogram, defaults=(batch, depth0))
        out["decision"] = decision.as_dict()
        # predicted-vs-measured for the config that actually ran, plus the
        # predicted effect of moving each knob alone to its chosen value
        base = cm.predict_seconds(histogram, batch, depth0, None)
        pred_cur = (n_rows / base) if base > 0 else None
        out["predicted_rows_per_sec_current"] = (
            round(pred_cur, 2) if pred_cur else None)
        out["measured_rows_per_sec"] = round(ips, 2)
        out["predicted_vs_measured_delta"] = (
            round((pred_cur - ips) / ips, 4) if pred_cur and ips else None)
        per_knob = {}
        for name, chosen, default in (
                ("mini_batch_size", decision.mini_batch_size, batch),
                ("prefetch_depth", decision.prefetch_depth, depth0),
                ("buckets",
                 None if decision.buckets is None
                 else list(decision.buckets), None)):
            cand = {"mini_batch_size": batch, "prefetch_depth": depth0,
                    "buckets": None}
            cand[name] = chosen
            sec = cm.predict_seconds(histogram, **cand)
            per_knob[name] = {
                "default": default, "chosen": chosen,
                "predicted_speedup": (round(base / sec, 4)
                                      if sec > 0 else None)}
        out["per_knob"] = per_knob

    # regression guard: best prior round of the same metric on the same
    # platform (a CPU-fallback round must not be judged against TPU rounds)
    best_prior, best_file = 0.0, None
    for path in priors:
        try:
            with open(path, encoding="utf-8") as fh:
                raw = json.load(fh)
        except (OSError, ValueError):
            continue
        parsed = raw.get("parsed") if isinstance(raw.get("parsed"), dict) \
            else (raw if "value" in raw else None)
        if not parsed or parsed.get("metric") != record.get("metric") \
                or parsed.get("platform") != record.get("platform"):
            continue
        v = parsed.get("value")
        if isinstance(v, (int, float)) and v > best_prior:
            best_prior, best_file = float(v), os.path.basename(path)
    tol = float(os.environ.get("BENCH_REGRESSION_TOLERANCE", "0.1"))
    if best_prior > 0:
        out["regression"] = {
            "best_prior": round(best_prior, 2),
            "best_prior_file": best_file, "tolerance": tol,
            "delta": round((ips - best_prior) / best_prior, 4),
            "dip": bool(ips < best_prior * (1.0 - tol))}
    else:
        out["regression"] = {"best_prior": None, "dip": False}
    return out


def main():
    t_start = time.monotonic()
    budget = float(os.environ.get("BENCH_WALL_BUDGET_S",
                                  str(DEFAULT_WALL_BUDGET_S)))

    def remaining() -> float:
        return budget - (time.monotonic() - t_start)

    record = {
        "metric": "resnet50_onnx_images_per_sec_per_chip",
        "value": 0.0, "unit": "images/sec/chip", "vs_baseline": 0.0,
        "platform": "unknown", "platform_raw": None, "device": None,
        "mfu": None, "device_resident_ips": None, "device_mfu": None,
        "device_resident_ips_fused": None, "device_mfu_fused": None,
        "h2d_gbps": None, "residency": None,
    }
    report = _OneShotReport(record, path=_partial_path())
    # registered once the model exists, so even a budget-truncated record
    # carries the stage counters measured so far
    counter_sources = []

    # device-stall watchdog: enabled for the whole bench run regardless of
    # MMLSPARK_TPU_WATCHDOG (env budget/interval/diag-dir knobs still
    # apply). A stall stamps the shared record with the bundle path and
    # checkpoints the partial JSON immediately — a later SIGKILL cannot
    # erase the verdict.
    from mmlspark_tpu.observability import configure_watchdog

    def _on_stall(stall: dict) -> None:
        record.setdefault("watchdog_stalls", []).append(
            {"site": stall.get("site"), "bundle": stall.get("bundle"),
             "stalled_seconds": stall.get("stalled_seconds"),
             "t": stall.get("t")})
        _fill_partial()
        report.checkpoint("watchdog_stall")

    configure_watchdog(enabled=True).on_stall(_on_stall)

    def _slo_card():
        # rolling scorecard of the run's phases + any serving traffic —
        # attached on EVERY exit path (budget watchdog, signals, clean end)
        try:
            from mmlspark_tpu.observability import get_tracker
            return get_tracker().scorecard()
        except Exception:               # noqa: BLE001
            return None

    def _telemetry():
        # stdlib-only registry snapshot: compile-cache hits/misses/
        # steady_state_recompiles plus aggregate stage counters, so the
        # perf trajectory carries observability data (docs/observability.md)
        from mmlspark_tpu.observability import snapshot
        return snapshot()

    def _residency():
        # data-plane residency scorecard: hit rate + transfer-op counts from
        # the residency layer, staging-slab churn, and the h2d-overlap
        # fraction (how much of coerce+pad host prep the prefetch worker hid
        # from the dispatch thread; 1.0 = prep fully overlapped transfers)
        try:
            from mmlspark_tpu.core.residency import residency_stats
            from mmlspark_tpu.models.runner import (M_SLAB_ALLOCS,
                                                    M_SLAB_REUSE)
            from mmlspark_tpu.ops.compile_cache import M_STAGE_SECONDS
            stats = residency_stats()
            allocs = M_SLAB_ALLOCS.labels().get()
            reuses = M_SLAB_REUSE.labels().get()
            issued = allocs + reuses
            prep_s = (M_STAGE_SECONDS.labels(stage="coerce").get()
                      + M_STAGE_SECONDS.labels(stage="pad").get())
            wait_s = M_STAGE_SECONDS.labels(stage="prefetch_wait").get()
            stats.update(
                staging_slab_allocs=allocs,
                staging_slab_reuses=reuses,
                staging_slab_reuse_rate=(
                    round(reuses / issued, 4) if issued else None),
                h2d_overlap_fraction=(
                    round(max(0.0, min(1.0, 1.0 - wait_s / prep_s)), 4)
                    if prep_s > 0 else None))
            return stats
        except Exception:               # noqa: BLE001
            return None

    def _fill_partial():
        # shared by the budget watchdog and the SIGTERM handler: fold in
        # whatever was measured before the interruption
        try:
            for snap in counter_sources:
                record["stage_counters"] = snap()
            record["telemetry"] = _telemetry()
            record["residency"] = _residency()
            record["slo"] = _slo_card()
            record["costs"] = _bench_costs(harvest=True)
            record["multi_model"] = _bench_multi_model()
        except Exception:                   # noqa: BLE001
            pass

    def _watchdog():
        time.sleep(max(1.0, budget))
        record["budget_truncated"] = True
        record.setdefault("midrun_error",
                          f"wall-clock budget {budget:.0f}s exhausted; "
                          "partial results reported")
        _fill_partial()
        if report.emit():
            os._exit(0)

    threading.Thread(target=_watchdog, daemon=True).start()
    _install_signal_handlers(report, _fill_partial)

    from mmlspark_tpu.ops.compile_cache import enable_persistent_cache
    record["compile_cache_dir"] = enable_persistent_cache()
    platform, device_kind = _init_backend()
    on_tpu = True
    record.update(platform=platform, platform_raw=platform,
                  device=device_kind)

    import jax

    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.models.onnx_model import ONNXModel
    from mmlspark_tpu.models.zoo.resnet import RESNET50, export_resnet_onnx

    batch = int(os.environ.get("BENCH_BATCH", "512"))
    n_rows = int(os.environ.get("BENCH_ROWS", "2048"))
    passes = int(os.environ.get("BENCH_PASSES", "3"))
    rng = np.random.default_rng(0)

    model_bytes = export_resnet_onnx(RESNET50, seed=0)
    # The input column holds what an image decoder produces: uint8 HWC.
    # Layout (NHWC→NCHW), dtype cast, and ImageNet normalization all run on
    # device fused into the graph — a uint8 image is 4x smaller than its
    # float32 tensor, and the host→device link is the bottleneck.
    m = ONNXModel(model_bytes,
                  feed_dict={"input": "image"},
                  fetch_dict={"logits": "logits"},
                  argmax_dict={"pred": "logits"},
                  transpose_dict={"input": [0, 3, 1, 2]},
                  normalize_dict={"input": {
                      "scale": 1.0 / 255.0,
                      "mean": [0.485, 0.456, 0.406],
                      "std": [0.229, 0.224, 0.225]}},
                  mini_batch_size=batch,
                  compute_dtype="bfloat16")
    counter_sources.append(m.stage_counters.snapshot)

    X = rng.integers(0, 256, (n_rows, 224, 224, 3), dtype=np.uint8)
    col = np.empty(n_rows, dtype=object)
    for i in range(n_rows):
        col[i] = X[i]
    df = DataFrame({"image": col})

    # AOT warm-up: every padding bucket the run will hit is compiled BEFORE
    # any timed section (full batches land in bucket_size(batch); a ragged
    # tail lands in its own bucket), so steady-state img/s excludes compile
    # by construction, not by hoping the first pass absorbed it. The
    # executables also persist to the compile cache for the next process.
    warm_sizes = sorted({batch, n_rows % batch or batch})
    with _phase_guard(record, "warm_up", min(remaining() - 90.0, 300.0),
                      report=report):
        try:
            t0 = time.perf_counter()
            record["warm_up"] = m.warm_up(
                batch_sizes=warm_sizes,
                input_specs={"input": (np.uint8, (224, 224, 3))})
            record["warm_up"]["wall_s"] = round(time.perf_counter() - t0, 3)
        except Exception as e:              # noqa: BLE001
            record["warm_up"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}

    # warmup transform: first full trip through the DataFrame path (host
    # transfers, drain) — timed as a last-resort number so even a run whose
    # timed passes all die still reports something real
    warm_ips = 0.0
    try:
        t0 = time.perf_counter()
        warm = m.transform(df.head(batch))
        warm_ips = batch / (time.perf_counter() - t0)
        assert len(warm) == batch
        # floor for a truncated record; the timed passes overwrite it
        record["value"] = round(warm_ips, 2)
        record["vs_baseline"] = round(warm_ips / TARGET_IMG_PER_SEC, 4)
    except Exception as e:              # noqa: BLE001
        # backend died before the warmup finished: still emit the one JSON
        # line the driver expects, with the reason, instead of crashing
        record["midrun_error"] = \
            f"warmup failed: {type(e).__name__}: {e}"[:300]
        record["stage_counters"] = m.stage_counters.snapshot()
        record["telemetry"] = _telemetry()
        record["residency"] = _residency()
        record["slo"] = _slo_card()
        record["costs"] = _bench_costs(harvest=True)
        record["multi_model"] = _bench_multi_model()
        report.emit()
        return

    # Several timed passes, with the observed host->device link speed
    # reported alongside; a pass that dies keeps the passes that DID
    # complete.
    #
    # The link probe STREAMS the same batches the pipeline sends (several
    # puts in flight) and runs interleaved between the e2e passes, so the
    # reported fraction-of-link compares numbers from the same window.
    import jax.numpy as jnp

    from mmlspark_tpu.observability import watch as _wd_watch

    def _h2d_streaming_gbps():
        parts = [X[lo:lo + batch] for lo in range(0, n_rows, batch)]
        with _wd_watch("bench_h2d_probe"):
            t0 = time.perf_counter()
            devs = [jax.device_put(a) for a in parts]
            for d in devs:
                float(jnp.sum(d[0, 0, 0, :].astype(jnp.float32)))   # fence
            el = time.perf_counter() - t0
        return sum(a.nbytes for a in parts) / el / 1e9

    ips = 0.0
    pass_ips = []
    h2d_samples = []
    midrun_error = None
    from mmlspark_tpu.observability import tracing as _tracing
    from mmlspark_tpu.ops.compile_cache import jit_cache_size
    cache_before_passes = jit_cache_size(m._jitted)
    with _phase_guard(record, "timed_passes", remaining() - 60.0,
                      report=report):
        for i in range(max(1, passes)):
            if remaining() < 45.0:
                # keep enough budget to assemble and emit the report; a
                # truncated run reports fewer passes, not nothing
                record["budget_truncated"] = True
                break
            if i > 0:
                # interleaved link probe in its OWN try: a probe failure
                # must neither abort the remaining e2e passes nor
                # masquerade as a pass failure (round-4 postmortem: an
                # optional leg's crash discarded a full TPU measurement)
                try:
                    h2d_samples.append(_h2d_streaming_gbps())
                except Exception:                   # noqa: BLE001
                    pass
            try:
                # each timed pass runs under a root trace: the flight
                # recorder keeps the per-stage span tree (coerce/pad on
                # the prefetch worker, h2d, dispatch, d2h) of every
                # measured pass, so a slow pass is diagnosable from the
                # emitted record alone
                root = _tracing.start_trace("bench.pass", index=i)
                t0 = time.perf_counter()
                with _tracing.activate(root):
                    out = m.transform(df)
                elapsed = time.perf_counter() - t0
                root.end(rows=n_rows)
                assert len(out) == n_rows
                pass_ips.append(n_rows / elapsed)
                ips = max(ips, pass_ips[-1])
                # keep the shared record current: a budget-truncated run
                # reports the best pass measured so far, not 0
                record["value"] = round(ips, 2)
                record["vs_baseline"] = round(ips / TARGET_IMG_PER_SEC, 4)
                record["best_of"] = len(pass_ips)
            except Exception as e:                  # noqa: BLE001
                midrun_error = f"pass failed: {type(e).__name__}: {e}"[:300]
                break
    if ips == 0.0:
        # warmup DID execute on device — report its rate (compile already
        # hoisted into warm_up) rather than discarding the run
        ips = warm_ips
    cache_after_passes = jit_cache_size(m._jitted)
    record["steady_state_recompiles"] = (
        cache_after_passes - cache_before_passes
        if cache_after_passes is not None and cache_before_passes is not None
        else None)
    try:
        record["pass_traces"] = [
            t.summary() for t in _tracing.get_flight_recorder().traces()
            if t.root is not None and t.root.name == "bench.pass"]
    except Exception:                   # noqa: BLE001
        pass

    # generation phase: the continuous-decoder trajectory number (paged KV,
    # chunked prefill, autotuner). Runs BEFORE the optional device probes:
    # a probe stalled inside one long native XLA call cannot be preempted
    # by the SIGALRM guard, and must not starve this phase -- it is the
    # number this bench exists to move. Own guard + own try so a failure
    # here never costs the image numbers above.
    with _phase_guard(record, "generation", min(remaining() - 30.0, 240.0),
                      report=report):
        try:
            if remaining() > 45.0:
                record["generation"] = _generation_phase(on_tpu)
            else:
                record["generation"] = {"skipped": "budget exhausted"}
        except Exception as e:          # noqa: BLE001
            record["generation"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}

    # multichip generation: the mesh-mounted engine vs single chip on the
    # same workload — tok/s vs chips, scaling efficiency, per-tick
    # collective estimate. Needs >= 2 devices (real or simulated); on one
    # device the phase records why it abstained instead of fake numbers.
    with _phase_guard(record, "multichip_generation",
                      min(remaining() - 25.0, 180.0), report=report):
        try:
            if jax.device_count() < 2:
                record["multichip_generation"] = {
                    "skipped": "single device"}
            elif remaining() > 40.0:
                record["multichip_generation"] = \
                    _multichip_generation_phase()
            else:
                record["multichip_generation"] = {
                    "skipped": "budget exhausted"}
        except Exception as e:          # noqa: BLE001
            record["multichip_generation"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}

    # failover phase: checkpoint/restore a live session cold and warm —
    # the drain-vs-kill handoff cost numbers, with token parity asserted
    with _phase_guard(record, "failover", min(remaining() - 25.0, 90.0),
                      report=report):
        try:
            if remaining() > 35.0:
                record["failover"] = _failover_phase()
            else:
                record["failover"] = {"skipped": "budget exhausted"}
        except Exception as e:          # noqa: BLE001
            record["failover"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}

    # scenarios phase: the smoke scenario open-loop against a 3-worker
    # in-process cluster — scorecard rows land in the ObservationStore
    # BEFORE the tuning phase reads it, so the tuner scores against
    # traffic-shaped observations from this very run
    with _phase_guard(record, "scenarios", min(remaining() - 25.0, 90.0),
                      report=report):
        try:
            if remaining() > 35.0:
                record["scenarios"] = {"smoke": _scenarios_phase(record)}
            else:
                record["scenarios"] = {"skipped": "budget exhausted"}
        except Exception as e:          # noqa: BLE001
            record["scenarios"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}

    # tuning phase: pure host arithmetic over this run's harvested samples
    # + the historical bench records — chosen config, per-knob predicted
    # deltas, and the trajectory regression guard
    with _phase_guard(record, "tuning", min(remaining() - 20.0, 60.0),
                      report=report):
        try:
            record["tuning"] = _tuning_phase(record, m, batch=batch,
                                             n_rows=n_rows, ips=ips)
            record["regression_flag"] = bool(
                (record["tuning"].get("regression") or {}).get("dip"))
        except Exception as e:          # noqa: BLE001
            record["tuning"] = {"error": f"{type(e).__name__}: {e}"[:200]}

    h2d_gbps = None
    link_bound_ips = None
    link_fraction = None
    device_ips = None
    device_ips_fused = None
    dev_setup = None
    mfu = None
    device_mfu = None
    device_mfu_fused = None
    # One guard over every optional device probe (h2d link, device-resident
    # rate, fused scan, XLA cost analysis): on a host where d2h crawls, any
    # one of these can silently eat the remaining budget -- the BENCH_r05
    # failure mode -- and starve the generation phase below.
    with _phase_guard(record, "device_probes",
                      min(remaining() - 90.0, 300.0), report=report):
        try:
            if not h2d_samples and remaining() > 30.0:
                h2d_samples.append(_h2d_streaming_gbps())
            if h2d_samples:
                h2d_gbps = round(max(h2d_samples), 3)
                bytes_per_img = 224 * 224 * 3
                link_bound_ips = round(h2d_gbps * 1e9 / bytes_per_img, 1)
                if link_bound_ips:
                    link_fraction = round(ips / link_bound_ips, 3)
        except Exception as e:              # noqa: BLE001
            if midrun_error is None:
                midrun_error = f"h2d probe failed: {type(e).__name__}: {e}"[:300]

        # Device-resident compute rate: what the chip sustains once inputs are
        # on device — separates the framework from the host->device link.
        # Fencing is a fetched scalar depending on the LAST dispatched call
        # (in-order device execution fences the earlier ones).
        try:
            if remaining() > 60.0:   # optional leg — skip under a tight budget
                import jax.numpy as jnp
                jitted = m._ensure_jitted()
                params = m._params_for_device(None)
                xdev = jax.device_put(X[:batch])
                rows_timed = int(xdev.shape[0])  # may be < batch when BENCH_ROWS is
                dev_setup = (jitted, params, xdev, rows_timed)
        except Exception:
            pass
        if dev_setup is not None:
            jitted, params, xdev, rows_timed = dev_setup
            try:
                with _wd_watch("bench_device_resident"):
                    tail = jax.jit(lambda c: jnp.sum(c["logits"][0, :2]
                                                     .astype(jnp.float32)))
                    float(tail(jitted(params,
                                      {"input": xdev})))   # compile + warm
                    reps = 20 if on_tpu else 3
                    t0 = time.perf_counter()
                    outs = None
                    for _ in range(reps):
                        outs = jitted(params, {"input": xdev})
                    float(tail(outs))
                device_ips = round(
                    rows_timed * reps / (time.perf_counter() - t0), 2)
            except Exception:
                pass

            # Fused-scan variant: R forwards inside ONE compiled program, each
            # iteration's input data-dependent on the previous output (the
            # carry perturbs the uint8 image, so XLA cannot hoist the
            # loop-invariant forward out of the scan). This isolates the
            # chip's sustained rate from the ~ms per-dispatch overhead this
            # runtime pays, which the per-dispatch loop above includes R times.
            try:
                if remaining() < 60.0:
                    raise TimeoutError("budget")
                R = 10

                @jax.jit
                def fused(params, x):
                    def body(t, _):
                        outs = jitted(params, {"input": x + t})
                        return (outs["pred"][0] % 2).astype(jnp.uint8), None
                    t, _ = jax.lax.scan(body, jnp.uint8(0), None, length=R)
                    return t
                with _wd_watch("bench_fused_scan"):
                    int(fused(params, xdev))               # compile + warm
                    # mean over reps, matching the per-dispatch loop's
                    # estimator — a best-of here would overstate the
                    # dispatch-overhead gap the two numbers exist to expose
                    reps_f = 3 if on_tpu else 1
                    t0 = time.perf_counter()
                    for _ in range(reps_f):
                        int(fused(params, xdev))           # fetched = fence
                    mean_f = (time.perf_counter() - t0) / reps_f
                device_ips_fused = round(rows_timed * R / mean_f, 2)
            except Exception:
                pass

        # MFU: per-image FLOPs straight from XLA's cost model for the compiled
        # program (not a hand-waved constant), peak from the device spec.
        try:
            if remaining() < 60.0:   # lower().compile() skips the jit cache —
                raise TimeoutError   # a full compile a truncated run can't pay
            import jax.numpy as jnp
            with _wd_watch("bench_cost_analysis"):
                compiled = m._jitted.lower(
                    m._params_for_device(None),
                    {"input": jnp.zeros((batch, 224, 224, 3),
                                        jnp.uint8)}).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            flops_per_img = float(cost.get("flops", 0.0)) / batch
            peak = peak_flops(device_kind)
            if flops_per_img:
                mfu = round(ips * flops_per_img / peak, 4)
                if device_ips:
                    device_mfu = round(device_ips * flops_per_img / peak, 4)
                if device_ips_fused:
                    device_mfu_fused = round(
                        device_ips_fused * flops_per_img / peak, 4)
        except Exception:
            mfu = None

    # mutate the watchdog-shared record in place — rebinding the name would
    # orphan the reference the budget thread emits on timeout
    record.update(
        value=round(ips, 2),
        vs_baseline=round(ips / TARGET_IMG_PER_SEC, 4),
        mfu=mfu,
        device_resident_ips=device_ips,
        device_mfu=device_mfu,
        device_resident_ips_fused=device_ips_fused,
        device_mfu_fused=device_mfu_fused,
        h2d_gbps=h2d_gbps,
        h2d_probe_kind="streaming-interleaved",
        link_bound_ips=link_bound_ips,
        link_fraction=link_fraction,
        best_of=len(pass_ips) if pass_ips else None,
        pass_spread=(round((max(pass_ips) - min(pass_ips))
                           / max(pass_ips), 3)
                     if pass_ips else None),
        stage_counters=m.stage_counters.snapshot(),
        telemetry=_telemetry(),
        residency=_residency(),
        slo=_slo_card(),
        costs=_bench_costs(harvest=True),
        multi_model=_bench_multi_model(),
        wall_s=round(time.monotonic() - t_start, 2),
    )
    if midrun_error is not None:
        record["midrun_error"] = midrun_error
    report.emit()


if __name__ == "__main__":
    main()
