"""Measurement-driven autotuning tests (ROADMAP item 4).

The contract under test: the observation store round-trips rows through its
append-only JSONL file and tolerates corrupt lines; the fitted cost model's
pick beats both endpoint configs of a synthetic skewed workload; a cold
model's measured sweep is bounded by the probe budget and every probe lands
in the store; ``BatchRunner(tuning="auto")`` applies the store's pick
end-to-end with ZERO steady-state recompiles after warming exactly the
chosen vocabulary (asserted through the compile-cache counters); and the
decision is reproducible from the persisted store alone.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.runner import BatchRunner
from mmlspark_tpu.ops.compile_cache import (M_STEADY_RECOMPILES,
                                            M_WARMUP_BUCKETS,
                                            warm_up_jitted)
from mmlspark_tpu.tuning import (CostModel, Observation, ObservationStore,
                                 candidate_configs, import_bench_records,
                                 measured_sweep, probe_budget, set_store)
from mmlspark_tpu.tuning.cost_model import (M_PROBES, PROBE_BUDGET_ENV,
                                            resolve_tuning)
from mmlspark_tpu.tuning.observations import harvest_samples


@pytest.fixture
def store():
    """A fresh in-memory store installed as the process-global one, so
    runner harvests and sweep probes in a test never leak across tests."""
    s = ObservationStore()
    set_store(s)
    yield s
    set_store(None)


def linear_rows(sig, *, alpha=0.01, beta=1e-4, prep=1e-5,
                buckets=(64, 128), batches=10):
    """Per-bucket samples lying exactly on sec/batch = alpha + beta*bucket."""
    out = []
    for b in buckets:
        out.append(Observation(
            sig=sig, source="runner", bucket=b, rows=b * batches,
            batches=batches, seconds=(alpha + beta * b) * batches,
            prep_seconds=prep * b * batches))
    return out


# ---------------------------------------------------------------------------
# observation store
# ---------------------------------------------------------------------------

class TestObservationStore:
    def test_round_trip(self, tmp_path):
        s1 = ObservationStore(str(tmp_path))
        s1.record_many(linear_rows("m1"))
        s1.record(Observation(sig="m2", source="probe", rows_per_sec=123.4,
                              config={"mini_batch_size": 32,
                                      "prefetch_depth": 1, "buckets": None}))
        # a second store over the same directory sees every row
        s2 = ObservationStore(str(tmp_path))
        assert len(s2) == 3
        assert s2.rows(sig="m1") == s1.rows(sig="m1")
        assert s2.rows(sig="m2")[0]["rows_per_sec"] == 123.4
        assert s2.signatures() == ["m1", "m2"]
        assert s2.corrupt_lines == 0

    def test_corrupt_lines_tolerated(self, tmp_path):
        s1 = ObservationStore(str(tmp_path))
        s1.record_many(linear_rows("m1"))
        path = os.path.join(str(tmp_path), "observations.jsonl")
        with open(path, "a", encoding="utf-8") as fh:
            fh.write("not json at all\n")
            fh.write(json.dumps({"no": "sig"}) + "\n")   # missing keys
            fh.write('{"sig": "torn", "source": "runn')  # torn tail
        s2 = ObservationStore(str(tmp_path))
        assert len(s2) == 2                 # the good rows survive
        assert s2.corrupt_lines == 3
        # the log is not poisoned: appends still work after a bad load
        s2.record(Observation(sig="m1", source="runner", bucket=32,
                              rows=32, batches=1, seconds=0.01))
        assert len(ObservationStore(str(tmp_path))) == 3

    def test_record_validates_required_keys(self, store):
        with pytest.raises(ValueError):
            store.record({"source": "runner"})          # no sig
        with pytest.raises(ValueError):
            store.record({"sig": "x"})                  # no source

    def test_filters(self, store):
        store.record_many(linear_rows("a"))
        store.record(Observation(sig="a", source="probe", placement="chip1",
                                 rows_per_sec=10.0))
        assert len(store.rows(sig="a", source="probe")) == 1
        assert len(store.rows(sig="a", placement="chip1")) == 1
        assert store.rows(sig="missing") == []

    def test_import_bench_records(self, tmp_path, store):
        wrapper = {"n": 4, "cmd": "python bench.py", "rc": 0, "tail": "",
                   "parsed": {"metric": "resnet50_onnx_images_per_sec_per_chip",
                              "value": 268.09, "platform": "tpu",
                              "stage_counters": {
                                  "compile": {"calls": 3, "seconds": 9.0}}}}
        raw = {"metric": "resnet50_onnx_images_per_sec_per_chip",
               "value": 9.13, "platform": "cpu"}
        crashed = {"n": 1, "rc": 1, "tail": "boom", "parsed": None}
        for name, payload in (("BENCH_r04.json", wrapper),
                              ("BENCH_r03.json", raw),
                              ("BENCH_r01.json", crashed)):
            with open(tmp_path / name, "w", encoding="utf-8") as fh:
                json.dump(payload, fh)
        n = import_bench_records(
            [str(tmp_path / f) for f in
             ("BENCH_r01.json", "BENCH_r03.json", "BENCH_r04.json",
              "BENCH_r99_missing.json")], store)
        assert n == 2                       # crashed + missing are skipped
        rows = store.rows(source="bench")
        assert sorted(r["rows_per_sec"] for r in rows) == [9.13, 268.09]
        assert rows[1]["compiles"] == 3 or rows[0]["compiles"] == 3

    def test_generation_observations_carry_paged_attn_impl(
            self, tmp_path, store):
        """Records with a generation phase yield an extra 'generation'
        observation stamped with the paged-attention impl, and
        compare_paged_attn turns them into per-placement speedups."""
        from mmlspark_tpu.tuning import compare_paged_attn

        def rec(val, tps, impl):
            return {"metric": "resnet50_onnx_images_per_sec_per_chip",
                    "value": val, "platform": "cpu", "device": "cpu",
                    "generation": {"tok_per_sec": tps, "tokens": 100,
                                   "wall_s": 1.0,
                                   "paged_attn": {"impl": impl}}}
        for name, payload in (("BENCH_r06.json", rec(5.0, 120.0, "kernel")),
                              ("BENCH_r07.json", rec(6.0, 80.0, "gather"))):
            with open(tmp_path / name, "w", encoding="utf-8") as fh:
                json.dump(payload, fh)
        n = import_bench_records(
            [str(tmp_path / "BENCH_r06.json"),
             str(tmp_path / "BENCH_r07.json")], store)
        assert n == 4                      # headline + generation per file
        gen = store.rows(sig="generation")
        assert sorted(r["paged_attn_impl"] for r in gen) \
            == ["gather", "kernel"]
        cmp = compare_paged_attn(store)
        assert cmp["cpu"]["kernel"]["tok_per_sec_mean"] == 120.0
        assert cmp["cpu"]["kernel_vs_gather_speedup"] == 1.5


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_fit_recovers_linear_coefficients(self):
        m = CostModel.fit(linear_rows("s", alpha=0.02, beta=5e-4,
                                      buckets=(32, 64, 128, 256)))
        assert m.alpha == pytest.approx(0.02, rel=1e-6)
        assert m.beta == pytest.approx(5e-4, rel=1e-6)
        assert m.prep_rate > 0

    def test_single_bucket_degrades_to_pure_slope(self):
        m = CostModel.fit(linear_rows("s", buckets=(64,)))
        assert m.alpha == 0.0
        assert m.beta > 0.0

    def test_pick_beats_both_endpoints(self):
        """Skewed workload: runs of 66 rows. The endpoints both lose —
        tiny batches pay the per-dispatch intercept 5x per run, the
        power-of-two default pads 66 up to 128 — so the model must pick
        something strictly cheaper than either."""
        m = CostModel.fit(linear_rows("s", alpha=0.01, beta=1e-4))
        hist = {66: 4}
        cands = candidate_configs(hist, defaults=(64, 2))
        lo = min(c[0] for c in cands)
        hi = max(c[0] for c in cands)
        pick = m.choose(hist, defaults=(64, 2))
        sec_pick = m.predict_seconds(hist, pick.mini_batch_size,
                                     pick.prefetch_depth, pick.buckets)
        sec_lo = m.predict_seconds(hist, lo, 2, None)    # many dispatches
        sec_hi = m.predict_seconds(hist, hi, 2, None)    # pow2 pad waste
        assert sec_pick < sec_lo
        assert sec_pick <= sec_hi
        # the pick pads nothing: the exact ladder covers the run size
        assert pick.buckets is not None
        assert 66 in pick.vocabulary

    def test_probe_rows_outrank_the_fit(self):
        rows = linear_rows("s")
        rows.append(Observation(
            sig="s", source="probe", rows_per_sec=1e6,
            config={"mini_batch_size": 16, "prefetch_depth": 0,
                    "buckets": None}))
        m = CostModel.fit(rows)
        # the directly-measured config predicts from its measurement
        assert m.predict_seconds({64: 1}, 16, 0, None) \
            == pytest.approx(64 / 1e6)

    def test_decision_reproducible_from_persisted_store(self, tmp_path):
        """Acceptance criterion: delete the model, re-fit from the JSONL
        alone, same pick."""
        s1 = ObservationStore(str(tmp_path))
        s1.record_many(linear_rows("s", alpha=0.02))
        d1 = CostModel.fit(s1.rows(sig="s")).choose({66: 4})
        del s1
        s2 = ObservationStore(str(tmp_path))
        d2 = CostModel.fit(s2.rows(sig="s")).choose({66: 4})
        assert d1.as_dict() == d2.as_dict()

    def test_resolve_tuning_cold_store_returns_none(self, store):
        assert resolve_tuning("never-seen", "default", {64: 1}) is None


# ---------------------------------------------------------------------------
# runner helpers shared by the sweep / e2e / acceptance tests
# ---------------------------------------------------------------------------

def _apply(params, feeds):
    return {"y": feeds["x"] @ params["w"]}


def _make_runner_factory(n_rows, din=8, dout=4, seed=0, **extra):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n_rows, din)).astype(np.float32)
    params = {"w": jnp.asarray(
        rng.normal(0, 0.5, (din, dout)).astype(np.float32))}
    jitted = jax.jit(_apply)

    def make(mini_batch_size, prefetch_depth, buckets):
        def coerce(sl):
            return {"x": X[sl]}
        return BatchRunner(jitted, params, coerce, jax.device_put,
                           mini_batch_size=mini_batch_size,
                           prefetch_depth=prefetch_depth, buckets=buckets,
                           **extra)
    return make, jitted, params


# ---------------------------------------------------------------------------
# measured sweep
# ---------------------------------------------------------------------------

class TestMeasuredSweep:
    def test_probe_budget_env(self, monkeypatch):
        monkeypatch.setenv(PROBE_BUDGET_ENV, "3")
        assert probe_budget() == 3
        monkeypatch.setenv(PROBE_BUDGET_ENV, "garbage")
        assert probe_budget() == 6          # default survives bad input

    def test_sweep_bounded_by_budget(self, store):
        make, _, _ = _make_runner_factory(40)
        cands = candidate_configs({40: 1}, defaults=(16, 1))
        assert len(cands) > 3               # the budget actually binds
        before = M_PROBES.labels().get()
        decision = measured_sweep(make, 40, sig="sweep-sig", budget=3,
                                  store=store)
        assert M_PROBES.labels().get() - before == 3
        probes = store.rows(sig="sweep-sig", source="probe")
        assert len(probes) == 3             # every probe became a row
        assert all(r["rows_per_sec"] > 0 for r in probes)
        # the decision came from the store the probes landed in
        assert decision.mini_batch_size >= 1
        assert decision.source in ("probe", "model")

    def test_sweep_decision_refittable_from_probes(self, store):
        make, _, _ = _make_runner_factory(40)
        d1 = measured_sweep(make, 40, sig="resweep", budget=4, store=store)
        d2 = CostModel.fit(store.rows(sig="resweep")).choose(
            {40: 1}, defaults=(64, 2))
        assert (d1.mini_batch_size, d1.prefetch_depth, d1.buckets) \
            == (d2.mini_batch_size, d2.prefetch_depth, d2.buckets)


# ---------------------------------------------------------------------------
# warm-up respects the active ladder (the power-of-two over-compile fix)
# ---------------------------------------------------------------------------

class TestWarmupLadder:
    def test_ladder_skips_buckets_outside_it(self, store):
        make, jitted, params = _make_runner_factory(66)
        specs = {"x": (np.dtype(np.float32), (8,))}
        before = M_WARMUP_BUCKETS.labels().get()
        # sizes 5 and 66 both land in the single ladder bucket 66; the
        # power-of-two ladder would compile 8 AND 128
        stats = warm_up_jitted(jitted, params, specs, [5, 66],
                               buckets=(66,))
        assert stats["buckets"] == [66]
        assert M_WARMUP_BUCKETS.labels().get() - before == 1

    def test_default_ladder_unchanged(self):
        make, jitted, params = _make_runner_factory(66, seed=3)
        specs = {"x": (np.dtype(np.float32), (8,))}
        before = M_WARMUP_BUCKETS.labels().get()
        stats = warm_up_jitted(jitted, params, specs, [5, 66])
        assert stats["buckets"] == [8, 128]
        assert M_WARMUP_BUCKETS.labels().get() - before == 2


# ---------------------------------------------------------------------------
# BatchRunner(tuning="auto") end-to-end + the acceptance criterion
# ---------------------------------------------------------------------------

class TestBatchRunnerAuto:
    def test_harvest_lands_in_store(self, store):
        make, _, _ = _make_runner_factory(40, model_sig="harvest-sig")
        runner = make(16, 1, None)
        runner.run_and_drain(40)
        rows = store.rows(sig="harvest-sig", source="runner")
        assert rows, "drain did not harvest samples"
        assert {r["bucket"] for r in rows} == {16, 8}   # 16+16+8 rows
        assert sum(r["rows"] for r in rows) == 40
        cfg = rows[0]["config"]
        assert cfg["mini_batch_size"] == 16
        assert cfg["prefetch_depth"] == 1

    def test_auto_applies_store_pick_with_zero_recompiles(self, store):
        """The acceptance loop: seed the store, warm exactly the chosen
        vocabulary, then run with tuning="auto" — the runner must adopt
        the pick and pay zero steady-state recompiles."""
        sig = "auto-sig"
        store.record_many(linear_rows(sig, alpha=0.01, beta=1e-4))
        expected = resolve_tuning(sig, "default", {66: 1},
                                  defaults=(64, 2), store=store)
        assert expected is not None
        make, jitted, params = _make_runner_factory(
            66, model_sig=sig, tuning="auto")
        specs = {"x": (np.dtype(np.float32), (8,))}
        warm_up_jitted(jitted, params, specs, expected.warm_up_sizes,
                       buckets=expected.buckets)
        runner = make(64, 2, None)
        before = M_STEADY_RECOMPILES.labels().get()
        out = runner.run_and_drain(66)
        # the pick was applied (not the 64/2 defaults it was built with)
        assert runner.decision is not None
        assert runner.mini_batch_size == expected.mini_batch_size
        assert runner.prefetch_depth == expected.prefetch_depth
        assert runner.buckets == expected.buckets
        # zero steady-state recompiles: warm-up covered the vocabulary
        assert M_STEADY_RECOMPILES.labels().get() - before == 0
        assert sum(b for _, b in out) == 66

    def test_autotuned_beats_defaults_on_skewed_workload(self, store):
        """Acceptance criterion end-to-end: on a skewed row-size workload
        (runs of 66 rows), the autotuned (ladder, mini_batch_size,
        prefetch_depth) moves strictly more rows/s through the SAME
        BatchRunner machinery than the power-of-two + 64/2 defaults, with
        zero steady-state recompiles, and the pick reproduces from the
        persisted store alone."""
        sig = "acc-sig"
        n = 66
        store.record_many(linear_rows(sig, alpha=0.01, beta=1e-4))
        decision = resolve_tuning(sig, "default", {n: 1},
                                  defaults=(64, 2), store=store)
        assert decision is not None
        # the tuned config avoids both failure modes: one dispatch per run
        # (not two) and zero pad rows (not 66 -> 64+2 buckets)
        assert decision.mini_batch_size >= n
        assert decision.buckets is not None

        make, jitted, params = _make_runner_factory(n, model_sig=sig)
        specs = {"x": (np.dtype(np.float32), (8,))}
        # warm both configs so neither measurement pays a compile: the
        # 64/2 default splits 66 rows into dispatches of 64 and 2
        warm_up_jitted(jitted, params, specs, [64, 2])
        warm_up_jitted(jitted, params, specs, decision.warm_up_sizes,
                       buckets=decision.buckets)

        default_runner = make(64, 2, None)
        tuned_runner = make(decision.mini_batch_size,
                            decision.prefetch_depth, decision.buckets)

        def best_rate(runner, reps=25, tries=3):
            best = 0.0
            for _ in range(tries):
                t0 = time.perf_counter()
                for _ in range(reps):
                    runner.run_and_drain(n)
                el = time.perf_counter() - t0
                best = max(best, n * reps / el)
            return best

        before = M_STEADY_RECOMPILES.labels().get()
        default_rate = best_rate(default_runner)
        tuned_rate = best_rate(tuned_runner)
        assert M_STEADY_RECOMPILES.labels().get() - before == 0
        assert tuned_rate > default_rate, (
            f"tuned {tuned_rate:.0f} rows/s !> default "
            f"{default_rate:.0f} rows/s")

        # reproducible from the persisted store alone: write the same
        # training rows to disk, re-fit cold, same pick
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            disk = ObservationStore(d)
            disk.record_many(linear_rows(sig, alpha=0.01, beta=1e-4))
            refit = CostModel.fit(
                ObservationStore(d).rows(sig=sig)).choose(
                    {n: 1}, defaults=(64, 2))
            assert (refit.mini_batch_size, refit.prefetch_depth,
                    refit.buckets) == (decision.mini_batch_size,
                                       decision.prefetch_depth,
                                       decision.buckets)

    def test_onnx_signature_stable_across_builds(self):
        """Two builds of the same graph serialize with different auto node
        names (builder names derive from object ids), so the signature
        must hash semantic content, not raw bytes — otherwise persisted
        decisions never match across processes."""
        from mmlspark_tpu.models.onnx_model import ONNXModel
        from mmlspark_tpu.onnx import model_content_digest

        alive = []      # a name is its node's address: no build may reuse one

        def build():
            import mmlspark_tpu.onnx as O
            rng = np.random.default_rng(7)
            w = rng.normal(0, 0.5, (8, 3)).astype(np.float32)
            nodes = [O.make_node("MatMul", ["x", "w"], ["logits"])]
            alive.append(nodes)
            graph = O.make_graph(
                nodes, "m",
                inputs=[O.make_tensor_value_info("x", np.float32,
                                                 ["N", 8])],
                outputs=[O.make_tensor_value_info("logits", np.float32,
                                                  ["N", 3])],
                initializers={"w": w})
            return O.make_model(graph)

        b1, b2 = build(), build()
        assert b1 != b2                     # names really do differ
        assert model_content_digest(b1) == model_content_digest(b2)
        m1 = ONNXModel(b1, feed_dict={"x": "f"}, fetch_dict={"logits": "o"},
                       pin_devices=False)
        m2 = ONNXModel(b2, feed_dict={"x": "f"}, fetch_dict={"logits": "o"},
                       pin_devices=False)
        assert m1.tuning_signature() == m2.tuning_signature()
        # different weights = different model = different signature
        b3 = build()[:-4] + b"\x00\x00\x80\x3f"   # perturb initializer tail
        assert model_content_digest(b3) != model_content_digest(b1)

    def test_ladder_validation(self):
        make, jitted, params = _make_runner_factory(40)
        with pytest.raises(ValueError):
            make(64, 2, (8, 16))            # mini_batch_size > max bucket
        with pytest.raises(ValueError):
            BatchRunner(jitted, params, lambda sl: {}, jax.device_put,
                        tuning="bogus")
