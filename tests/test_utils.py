"""Tests for runtime utilities (reference: ClusterUtil, FaultToleranceUtils,
AsyncUtils, SharedVariable — SURVEY.md §2.1 core/utils row)."""

import os
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from mmlspark_tpu.utils import (SharedSingleton,
                                SharedVariable,
                                StopWatch,
                                device_for_partition,
                                global_devices,
                                local_devices,
                                map_buffered,
                                num_tasks,
                                retry_with_backoff,
                                retry_with_timeout)


def test_cluster_topology():
    assert len(global_devices()) == 8  # virtual CPU mesh from conftest
    assert num_tasks() == 8
    assert num_tasks(3) == 3
    devs = local_devices()
    assert device_for_partition(0) == devs[0]
    assert device_for_partition(len(devs)) == devs[0]


def test_retry_with_timeout():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_with_timeout(flaky, timeout_s=5, retries=5) == "ok"
    with pytest.raises(RuntimeError):
        retry_with_timeout(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                           timeout_s=1, retries=2)


def test_retry_with_backoff():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise ValueError("no")
        return 42

    assert retry_with_backoff(flaky, waits_ms=[0, 1, 1]) == 42


def test_buffered_await_order():
    out = list(map_buffered(lambda x: x * x, range(10), concurrency=3))
    assert out == [x * x for x in range(10)]


def test_shared_variable_single_creation():
    count = []
    sv = SharedVariable(lambda: count.append(1) or "v")
    assert sv.get() == "v" and sv.get() == "v"
    assert len(count) == 1
    SharedSingleton.reset()
    a = SharedSingleton.get("k", lambda: object())
    b = SharedSingleton.get("k", lambda: object())
    assert a is b


def test_stopwatch():
    sw = StopWatch()
    with sw:
        time.sleep(0.01)
    assert sw.elapsed_s >= 0.01
    sw.measure(lambda: time.sleep(0.005))
    assert sw.elapsed_s >= 0.015


class TestDeviceDetection:
    """One is_tpu() for every TPU gate: scattered `== "tpu"` string checks
    would each decide alone what the process runs on."""

    def test_is_tpu_false_on_cpu(self):
        from mmlspark_tpu.utils import device
        assert device.is_tpu() is False       # conftest pins CPU backend
        platform, kind = device.device_info()
        assert platform == "cpu"

    def test_force_override(self, monkeypatch):
        from mmlspark_tpu.utils import device
        monkeypatch.setenv("MMLSPARK_TPU_FORCE_PLATFORM", "tpu")
        assert device.is_tpu() is True
        monkeypatch.setenv("MMLSPARK_TPU_FORCE_PLATFORM", "cpu")
        assert device.is_tpu() is False

    def test_generation_none_off_tpu(self):
        from mmlspark_tpu.utils import device
        assert device.tpu_generation() is None

    def test_gates_follow_is_tpu(self, monkeypatch):
        """flash-attention interpret mode and the Pallas histogram gate
        both funnel through is_tpu()."""
        from mmlspark_tpu.ops import pallas_kernels
        from mmlspark_tpu.ops.flash_attention import _auto_interpret
        monkeypatch.setenv("MMLSPARK_TPU_FORCE_PLATFORM", "tpu")
        assert _auto_interpret() is False
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS", raising=False)
        assert pallas_kernels.histogram_enabled() is True
        monkeypatch.setenv("MMLSPARK_TPU_FORCE_PLATFORM", "cpu")
        assert _auto_interpret() is True
        assert pallas_kernels.histogram_enabled() is False

    def test_backend_failure_propagates(self, monkeypatch):
        """A backend that cannot come up is an error, never "not a TPU":
        the kernel gates must not turn it into interpret mode."""
        import jax

        from mmlspark_tpu.parallel import mesh
        from mmlspark_tpu.utils import device

        def boom(*a, **kw):
            raise RuntimeError("backend init failed")

        monkeypatch.delenv("MMLSPARK_TPU_FORCE_PLATFORM", raising=False)
        monkeypatch.setattr(device, "_CACHE", None)
        monkeypatch.setattr(jax, "devices", boom)
        monkeypatch.setattr(jax, "local_devices", boom)
        with pytest.raises(RuntimeError, match="backend init failed"):
            device.is_tpu()
        with pytest.raises(RuntimeError, match="backend init failed"):
            mesh.local_devices()
        with pytest.raises(RuntimeError, match="backend init failed"):
            mesh.device_for_partition(0)

    def test_force_cpu_sets_env_for_children(self, monkeypatch):
        import os

        from mmlspark_tpu.utils import device
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        device.force_cpu()
        assert os.environ["JAX_PLATFORMS"] == "cpu"


class TestPersistentCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        # these are PROCESS-GLOBAL jax settings: leak one test's tmp_path
        # cache dir and every later compile in this process writes there
        import jax
        saved = (jax.config.jax_compilation_cache_dir,
                 jax.config.jax_persistent_cache_min_entry_size_bytes,
                 jax.config.jax_persistent_cache_min_compile_time_secs)
        yield
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          saved[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[2])

    def test_enable_sets_jax_config(self, tmp_path, monkeypatch):
        import jax

        from mmlspark_tpu.ops.compile_cache import enable_persistent_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = tmp_path / "xla-cache"
        assert enable_persistent_cache(str(d)) == str(d)
        assert jax.config.jax_compilation_cache_dir == str(d)
        assert d.is_dir()

    def test_off_until_enabled(self, monkeypatch):
        """Importing the package with no cache variable set turns nothing
        on: a library must not write to disk unasked."""
        import subprocess
        import sys
        code = ("import mmlspark_tpu, jax\n"
                "print(repr(jax.config.jax_compilation_cache_dir))\n")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = REPO
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-500:]
        assert r.stdout.strip() == "None"

    def test_cross_process_warmup_drops(self, tmp_path):
        """The point of the cache: a second process re-running the same
        jitted program must start measurably faster (executables are
        reloaded from disk instead of recompiled). Placed from outside by
        JAX's own variable; importing the package zeroes the size gates."""
        import subprocess
        import sys

        child = (
            "import time\n"
            "import jax\n"
            "import mmlspark_tpu\n"
            "import jax.numpy as jnp\n"
            "t0 = time.perf_counter()\n"
            "f = jax.jit(lambda x: (x @ x.T).sum())\n"
            "float(f(jnp.arange(256*64, dtype=jnp.float32)"
            ".reshape(256, 64)))\n"
            "print('compile_s=%.3f' % (time.perf_counter() - t0))\n")
        cache = tmp_path / "cc"
        env = {**os.environ, "PYTHONPATH": REPO,
               "JAX_COMPILATION_CACHE_DIR": str(cache)}
        times = []
        for _ in range(2):
            r = subprocess.run([sys.executable, "-c", child], env=env,
                               capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr[-500:]
            times.append(float(r.stdout.strip().split("compile_s=")[1]))
        assert times[1] < times[0]
        assert any(cache.iterdir())
