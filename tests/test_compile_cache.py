"""AOT warm-up, persistent compilation cache, and feed/drain pipeline tests.

The contract under test: ``warm_up`` populates the jit executable cache for
every declared padding bucket so the first real batch of each bucket pays
zero compiles; the overlapped drain preserves row order (including a ragged
last batch) under prefetch; ``StageCounters`` account the pipeline stages;
``ONNXModel.set`` invalidates cached device params on any jit-visible change
(the ``compute_dtype`` regression); the serving engine runs its pre-serve
warm-up hook before draining traffic.
"""

import os
import threading
import time

import numpy as np
import pytest

import mmlspark_tpu.onnx as O
from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.onnx_model import ONNXModel
from mmlspark_tpu.ops import compile_cache as cc
from mmlspark_tpu.ops.compile_cache import (StageCounters,
                                            enable_persistent_cache,
                                            jit_cache_size,
                                            resolve_input_specs)


def mlp_bytes(din=8, dout=3, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 0.5, (din, dout)).astype(np.float32)
    b = rng.normal(0, 0.1, dout).astype(np.float32)
    nodes = [O.make_node("MatMul", ["x", "w"], ["h"]),
             O.make_node("Add", ["h", "b"], ["logits"])]
    graph = O.make_graph(
        nodes, "mlp",
        inputs=[O.make_tensor_value_info("x", np.float32, ["N", din])],
        outputs=[O.make_tensor_value_info("logits", np.float32,
                                          ["N", dout])],
        initializers={"w": w, "b": b})
    return O.make_model(graph), (w, b)


def mlp_onnx_model(n_parts=1, **kw):
    data, (w, b) = mlp_bytes()
    kw.setdefault("pin_devices", False)
    kw.setdefault("mini_batch_size", 8)
    m = ONNXModel(data, feed_dict={"x": "feats"},
                  fetch_dict={"logits": "logits"}, **kw)
    return m, (w, b)


def feats_df(n, din=8, seed=1, npartitions=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, din)).astype(np.float32)
    return DataFrame({"feats": [X[i] for i in range(n)]},
                     npartitions=npartitions), X


class TestStageCounters:
    def test_add_and_snapshot(self):
        c = StageCounters()
        c.add("h2d", 0.5, nbytes=100)
        c.add("h2d", 0.25, nbytes=50)
        c.add("compile", 1.0, count=3)
        snap = c.snapshot()
        assert snap["h2d"] == {"calls": 2, "seconds": 0.75, "bytes": 150}
        assert snap["compile"]["calls"] == 3
        assert c.total_seconds("h2d") == pytest.approx(0.75)
        assert c.total_seconds("missing") == 0.0

    def test_timer_context(self):
        c = StageCounters()
        with c.timer("pad", nbytes=7):
            time.sleep(0.01)
        snap = c.snapshot()
        assert snap["pad"]["calls"] == 1
        assert snap["pad"]["bytes"] == 7
        assert snap["pad"]["seconds"] >= 0.005

    def test_reset(self):
        c = StageCounters()
        c.add("d2h", 1.0)
        c.reset()
        assert c.snapshot() == {}

    def test_thread_safety(self):
        c = StageCounters()

        def work():
            for _ in range(500):
                c.add("x", 0.001, nbytes=1)

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = c.snapshot()
        assert snap["x"]["calls"] == 4000
        assert snap["x"]["bytes"] == 4000


@pytest.fixture
def cache_config_guard():
    """Restore the persistent-cache wiring after a test mutates it."""
    import jax
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_entry_size_bytes,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", prev[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[2])


@pytest.fixture
def config_writes(monkeypatch):
    """Names the program writes through ``jax.config.update``."""
    import jax
    written = []
    real = jax.config.update

    def spy(name, value):
        written.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return written


class TestPersistentCache:
    def test_explicit_dir(self, tmp_path, monkeypatch, cache_config_guard):
        import jax
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        d = str(tmp_path / "xla-cache")
        assert enable_persistent_cache(d) == d
        assert cc.persistent_cache_dir() == d
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)
        # idempotent re-enable
        assert enable_persistent_cache(d) == d

    def test_default_is_one_fixed_path_in_the_checkout(
            self, monkeypatch, cache_config_guard, config_writes):
        import jax
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        monkeypatch.setattr(os, "makedirs", lambda *a, **kw: None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        assert enable_persistent_cache() == cc.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_CACHE_DIR
        assert "jax_compilation_cache_dir" in config_writes

    def test_jax_variable_places_the_cache(self, tmp_path, monkeypatch,
                                           cache_config_guard,
                                           config_writes):
        """Where JAX_COMPILATION_CACHE_DIR is set the program uses it and
        sets no directory in code — not even an explicit argument."""
        d = str(tmp_path / "from-outside")
        monkeypatch.setenv(cc.CACHE_DIR_ENV, d)
        assert enable_persistent_cache() == d
        assert enable_persistent_cache(str(tmp_path / "ignored")) == d
        assert "jax_compilation_cache_dir" not in config_writes
        assert not os.path.exists(str(tmp_path / "ignored"))
        # the size/time gates are still zeroed so small programs are kept
        assert "jax_persistent_cache_min_entry_size_bytes" in config_writes

    @pytest.mark.parametrize("name", ["MMLSPARK_TPU_COMPILE_CACHE_DIR",
                                      "MMLSPARK_TPU_COMPILE_CACHE"])
    def test_private_names_are_gone(self, name, tmp_path, monkeypatch,
                                    cache_config_guard):
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        monkeypatch.setenv(name, str(tmp_path / "private"))
        monkeypatch.setattr(os, "makedirs", lambda *a, **kw: None)
        assert enable_persistent_cache() == cc.DEFAULT_CACHE_DIR
        assert not os.path.exists(str(tmp_path / "private"))

    def test_warm_up_leaves_the_cache_alone(self, monkeypatch,
                                            cache_config_guard,
                                            config_writes):
        """warm_up compiles; it does not decide where (or whether) the
        persistent cache lives — the entry points do."""
        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        m, _ = mlp_onnx_model(mini_batch_size=4)
        m.warm_up(batch_sizes=[4])
        assert "jax_compilation_cache_dir" not in config_writes


class TestResolveInputSpecs:
    def _vi(self, name, dtype, shape):
        class VI:
            pass

        v = VI()
        v.name, v.numpy_dtype, v.shape = name, dtype, shape
        return v

    def test_plain(self):
        specs = resolve_input_specs([self._vi("x", np.float32, ["N", 8])],
                                    {"x": "feats"}, {})
        assert specs == {"x": (np.dtype(np.float32), (8,))}

    def test_unfed_inputs_skipped(self):
        specs = resolve_input_specs([self._vi("x", np.float32, ["N", 8]),
                                     self._vi("state", np.float32, ["N", 4])],
                                    {"x": "feats"}, {})
        assert list(specs) == ["x"]

    def test_transpose_inverted(self):
        # graph declares NCHW; the column feeds NHWC via transpose_dict
        specs = resolve_input_specs(
            [self._vi("img", np.float32, ["N", 3, 224, 224])],
            {"img": "image"}, {"img": [0, 3, 1, 2]})
        assert specs["img"] == (np.dtype(np.float32), (224, 224, 3))

    def test_symbolic_shape_raises(self):
        with pytest.raises(ValueError, match="input_specs"):
            resolve_input_specs([self._vi("x", np.float32, ["N", "D"])],
                                {"x": "feats"}, {})

    def test_override_wins(self):
        specs = resolve_input_specs(
            [self._vi("x", np.float32, ["N", "D"])], {"x": "feats"}, {},
            overrides={"x": (np.uint8, (5,))})
        assert specs["x"] == (np.dtype(np.uint8), (5,))

    def test_transpose_rank_mismatch(self):
        with pytest.raises(ValueError, match="permutes"):
            resolve_input_specs(
                [self._vi("img", np.float32, ["N", 3, 4])],
                {"img": "image"}, {"img": [0, 2, 3, 1]})


class TestWarmUp:
    def test_every_bucket_compiled_no_recompile_on_traffic(self):
        m, (w, b) = mlp_onnx_model(mini_batch_size=8)
        stats = m.warm_up(batch_sizes=[8, 3])
        # 3 pads to bucket 4, 8 stays 8 → two distinct compiled shapes
        assert stats["buckets"] == [4, 8]
        assert stats["compiles"] == 2
        assert stats["placements"] == 1
        jitted = m._ensure_jitted()
        size_after_warm = jit_cache_size(jitted)
        assert size_after_warm is not None and size_after_warm >= 2

        # 11 rows @ batch 8 → slices of 8 and 3: both buckets pre-warmed,
        # so real traffic must hit the cache every time
        df, X = feats_df(11)
        out = m.transform(df)
        assert jit_cache_size(jitted) == size_after_warm
        np.testing.assert_allclose(np.stack(list(out["logits"])),
                                   X @ w + b, rtol=1e-4, atol=1e-4)

    def test_default_sizes_use_mini_batch_size(self):
        m, _ = mlp_onnx_model(mini_batch_size=16)
        stats = m.warm_up()
        assert stats["buckets"] == [16]

    def test_warm_up_counts_compile_stage(self):
        m, _ = mlp_onnx_model()
        m.warm_up(batch_sizes=[8])
        snap = m.stage_counters.snapshot()
        assert snap["compile"]["calls"] >= 1
        assert snap["compile"]["seconds"] > 0

    def test_background_warm_up(self):
        m, _ = mlp_onnx_model()
        t = m.warm_up(batch_sizes=[8], background=True)
        assert isinstance(t, threading.Thread)
        t.join(timeout=60)
        assert not t.is_alive()
        assert jit_cache_size(m._ensure_jitted()) >= 1

    def test_unwarmed_bucket_counts_as_compile(self):
        m, _ = mlp_onnx_model(mini_batch_size=8)
        m.warm_up(batch_sizes=[8])
        df, _ = feats_df(3)   # bucket 4 — deliberately NOT warmed
        m.transform(df)
        snap = m.stage_counters.snapshot()
        # the cold bucket's stall is attributed to "compile", not "dispatch"
        assert snap["compile"]["calls"] >= 2  # 1 warm-up + 1 cold traffic

    def test_jax_model_warm_up(self):
        params = {"w": np.eye(4, dtype=np.float32)}

        def apply(p, feeds):
            return {"y": feeds["input"] @ p["w"]}

        m = JaxModel(apply, params, feed_dict={"input": "feats"},
                     mini_batch_size=4, pin_devices=False)
        stats = m.warm_up(input_specs={"input": (np.float32, (4,))},
                          batch_sizes=[4])
        assert stats["buckets"] == [4]
        assert stats["compiles"] == 1
        jitted = m._ensure_jitted()
        size = jit_cache_size(jitted)
        rng = np.random.default_rng(0)
        X = rng.normal(0, 1, (4, 4)).astype(np.float32)
        df = DataFrame({"feats": [X[i] for i in range(4)]})
        out = m.transform(df)
        assert jit_cache_size(jitted) == size  # no recompile on first batch
        np.testing.assert_allclose(np.stack(list(out["y"])), X,
                                   rtol=1e-5, atol=1e-5)


class TestDrainOrdering:
    @pytest.mark.parametrize("prefetch_depth", [0, 2])
    def test_row_order_and_ragged_tail(self, prefetch_depth):
        # 37 rows / batch 8 / 2 partitions → several full batches plus a
        # ragged tail per partition; values are row-indexed so any
        # reordering or tail corruption shows up as a value mismatch
        m, (w, b) = mlp_onnx_model(mini_batch_size=8,
                                   prefetch_depth=prefetch_depth)
        df, X = feats_df(37, npartitions=2)
        out = m.transform(df)
        assert len(out) == 37
        np.testing.assert_allclose(np.stack(list(out["logits"])),
                                   X @ w + b, rtol=1e-4, atol=1e-4)

    def test_single_row_partition(self):
        m, (w, b) = mlp_onnx_model(mini_batch_size=8, prefetch_depth=2)
        df, X = feats_df(1)
        out = m.transform(df)
        np.testing.assert_allclose(np.stack(list(out["logits"])),
                                   X @ w + b, rtol=1e-4, atol=1e-4)

    def test_stage_counters_populated(self):
        m, _ = mlp_onnx_model(mini_batch_size=8)
        df, _ = feats_df(20)
        m.transform(df)
        snap = m.stage_counters.snapshot()
        for stage in ["coerce", "pad", "h2d", "d2h"]:
            assert snap[stage]["calls"] >= 1, stage
        assert snap["h2d"]["bytes"] > 0
        assert snap["d2h"]["bytes"] > 0
        # every dispatch was either a hit (dispatch) or a compile
        assert (snap.get("dispatch", {}).get("calls", 0)
                + snap["compile"]["calls"]) >= 3


class TestSetInvalidation:
    def test_compute_dtype_change_invalidates_device_params(self):
        import jax.numpy as jnp
        m, _ = mlp_onnx_model()
        df, _ = feats_df(8)
        m.transform(df)
        assert m._device_params  # populated by the run
        key = next(iter(m._device_params))
        assert m._device_params[key]["w"].dtype == jnp.float32

        m.set(compute_dtype="bfloat16")
        # the regression: this cache used to survive a compute_dtype change,
        # leaving f32-cast weights serving a bf16 run
        assert m._device_params == {}
        m.transform(df)
        key = next(iter(m._device_params))
        assert m._device_params[key]["w"].dtype == jnp.bfloat16

    def test_unrelated_set_keeps_cache(self):
        m, _ = mlp_onnx_model()
        df, _ = feats_df(8)
        m.transform(df)
        cached = dict(m._device_params)
        m.set(mini_batch_size=4)
        assert m._device_params == cached


class TestServingEngineWarmUpHook:
    def test_hook_runs_before_serving(self):
        from mmlspark_tpu.serving.engine import ServingEngine
        calls = []
        eng = ServingEngine(lambda df: df, warm_up=lambda: calls.append(1))
        try:
            eng.start()
            assert calls == [1]
        finally:
            eng.stop()

    def test_hook_failure_is_not_fatal(self):
        from mmlspark_tpu.serving.engine import ServingEngine

        def boom():
            raise RuntimeError("no device")

        eng = ServingEngine(lambda df: df, warm_up=boom)
        try:
            eng.start()
            assert any(t.is_alive() for t in eng._threads)
        finally:
            eng.stop()
