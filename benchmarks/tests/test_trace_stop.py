"""Who stops the profiler and when: a traced run's main thread, after
``trace_seconds`` where the cell names them (the window runs on beside it),
after the window where it does not; an untraced run starts no thread and
calls nothing of the profiler."""

import threading
import time
import types

import jax
import pytest

from benchmarks import run

from . import tiny

GENERATE = ("gpt2xl_generate_closed", "generate")
TRANSFORM = ("resnet50_transform_resident", "transform")


def watched(driver, where):
    """A cell's own driver, noting the thread its window runs on."""
    base = run.load_by_path("drivers", driver).Driver

    class Watched(base):
        def window(self, seconds):
            where.append(threading.current_thread())
            return base.window(self, seconds)
    return Watched


class Forbidden:
    def __init__(self, what):
        self.what = what

    def __getattr__(self, name):
        raise AssertionError(f"{self.what}.{name} in an untraced run")

    def __call__(self, *args, **kwargs):
        raise AssertionError(f"{self.what}() in an untraced run")


@pytest.fixture
def stops(monkeypatch):
    """``[(thread, perf_counter), ...]`` of every ``stop_trace``."""
    calls, stop = [], jax.profiler.stop_trace

    def stopping():
        calls.append((threading.current_thread(), time.perf_counter()))
        stop()
    monkeypatch.setattr(jax.profiler, "stop_trace", stopping)
    return calls


def test_a_named_stretch_is_stopped_from_the_main_thread_inside_the_window(
        stops):
    where, seconds = [], 2.5
    line, before = tiny.run_cell(GENERATE[0], seconds=seconds, trace=1,
                                 driver_override=watched(GENERATE[1], where))
    assert [t for t, _ in stops] == [threading.main_thread()]
    assert where and where[0] is not threading.main_thread()
    cost = next(ln for ln in before if "stop_trace_s" in ln)
    stretch = tiny.SHRINK["generate"]["cell"]["trace_seconds"]
    assert stretch <= cost["traced_s"] < stretch + 0.1
    assert cost["stop_trace_s"] > 0 and cost["trace_bytes"] > 0
    assert "device_op_events" in cost
    # the window kept its own length, and the stop fell inside it
    facts = next(ln for ln in before if "window_elapsed_s" in ln)
    assert seconds <= facts["window_elapsed_s"] < seconds + 0.2
    assert line["correct"] is True and line["attempted"] > 0
    assert isinstance(line["breakdown"]["idle_gaps"], list)
    assert "ticks_per_s.generate" in line["metrics"]


def test_a_cell_with_no_stretch_is_stopped_after_its_window(stops,
                                                            monkeypatch):
    monkeypatch.setattr(run, "futures", Forbidden("futures"))
    where = []
    line, before = tiny.run_cell(TRANSFORM[0], seconds=1.0, trace=1,
                                 driver_override=watched(TRANSFORM[1], where))
    assert where == [threading.main_thread()]
    assert [t for t, _ in stops] == [threading.main_thread()]
    cost = next(ln for ln in before if "stop_trace_s" in ln)
    facts = next(ln for ln in before if "window_elapsed_s" in ln)
    assert cost["traced_s"] >= facts["window_elapsed_s"] >= 1.0
    assert line["correct"] is True


@pytest.mark.parametrize("workload,driver", [GENERATE, TRANSFORM])
def test_an_untraced_run_starts_no_thread_and_calls_no_profiler(
        workload, driver, monkeypatch):
    monkeypatch.setattr(run, "futures", Forbidden("futures"))
    monkeypatch.setattr(run, "Trace", Forbidden("Trace"))
    for name in ("start_trace", "stop_trace", "ProfileOptions"):
        monkeypatch.setattr(jax.profiler, name,
                            Forbidden(f"jax.profiler.{name}"))
    where = []
    line, _ = tiny.run_cell(workload, seconds=1.0, trace=0,
                            driver_override=watched(driver, where))
    assert where == [threading.main_thread()]
    assert line["correct"] is True and "breakdown" not in line


@pytest.fixture
def trace(tmp_path):
    """A ``Trace`` over a profiler that does nothing."""
    profiler = types.SimpleNamespace(
        ProfileOptions=types.SimpleNamespace,
        start_trace=lambda *a, **k: None, stop_trace=lambda: None)
    return run.Trace(types.SimpleNamespace(profiler=profiler),
                     str(tmp_path / "trace"))


def test_what_the_window_raises_reaches_the_main_thread(trace):
    def window(seconds):
        raise RuntimeError("a client did not finish")
    with pytest.raises(RuntimeError, match="did not finish"):
        trace.over(window, 1.0, 0.05)
    assert trace.t1 is not None     # stopped all the same


def test_a_window_shorter_than_the_stretch_ends_the_trace(trace):
    assert trace.over(lambda seconds: "done", 0.0, 30.0) == "done"
    assert trace.t1 - trace.t0 < 5.0
