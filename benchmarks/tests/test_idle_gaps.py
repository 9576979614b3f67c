"""Idle-gap attribution on a small synthetic xplane, the two transform
shares against the idle share, and the old reduction untouched by it."""

import jax
import pytest

from benchmarks import idle_gaps, run, trace_reduce

from . import tiny
from .test_trace_reduce import XSPACE

# device busy [1000, 5000] and [11000, 12000] ns (test_trace_reduce); a
# host plane as the profiler writes it with the host tracer on, and the
# start time the span log's clock is tied to
TWO_THREADS = XSPACE[:XSPACE.index('planes {\n  name: "/host:CPU"')] + '''
planes {
  name: "/host:CPU"
  lines {
    name: "partition-0/11"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9500000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 10500000 duration_ps: 4000000 }
  }
  lines {
    name: "prefetch/12"
    timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 4500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "runner.run#rows=8#" } }
  event_metadata { key: 2 value { id: 2 name: "runner.next" } }
  event_metadata { key: 3 value { id: 3 name: "runner.d2h#batches=2#" } }
  event_metadata { key: 4 value { id: 4 name: "runner.coerce" } }
}
planes {
  name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 1700000000000000000 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
}
'''
ORIGIN = 1700000000000000000


@pytest.fixture(scope="module")
def profile():
    return jax.profiler.ProfileData.from_text_proto(TWO_THREADS)


#: what the program's span log holds of the same two threads
LOG = [("runner.run", 11, ORIGIN + 500, ORIGIN + 10000),
       ("runner.next", 11, ORIGIN + 4000, ORIGIN + 7000),
       ("runner.d2h", 11, ORIGIN + 10500, ORIGIN + 14500),
       ("runner.coerce", 12, ORIGIN + 4500, ORIGIN + 5500)]


@pytest.fixture(scope="module")
def found(profile):
    from mmlspark_tpu.observability import tracing
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tracing, "span_log", lambda: LOG)
        # the traced stretch: 0 .. 16000 ns of the trace
        return idle_gaps.analyse(profile, [ORIGIN, ORIGIN + 16000])


def test_the_old_reduction_reads_the_same_keys_and_numbers(profile):
    reduced = trace_reduce.reduce_profile(profile, window_s=20e-6)
    assert sorted(reduced) == [
        "busy_s", "collective_exposed_s", "collective_s", "devices",
        "module_ops", "modules", "ops", "window_s"]
    assert reduced["busy_s"] == pytest.approx(5e-6)
    assert reduced["ops"]["fusion"] == [pytest.approx(3e-6), 2]


def test_device_idle_intervals(profile, found):
    assert idle_gaps.device_busy(profile) == [[(1000, 5000), (11000, 12000)]]
    assert found["gaps"] == [[(0, 1000), (5000, 11000), (12000, 16000)]]
    assert found["idle_s"] == pytest.approx(11e-6)


def test_a_gap_goes_to_the_innermost_span_on_each_thread(found):
    # [0, 1000]: no span open anywhere. [5000, 11000]: the partition thread
    # is in runner.next inside runner.run, the worker in runner.coerce: the
    # gap counts under both. [12000, 16000]: runner.d2h
    assert found["by_name"] == {
        "unattributed": pytest.approx(1e-6),
        "runner.next": pytest.approx(6e-6),
        "runner.coerce": pytest.approx(6e-6),
        "runner.d2h": pytest.approx(4e-6)}
    assert found["named_s"] == pytest.approx(10e-6)     # a gap counts once
    assert found["top"][0][1] == pytest.approx(6e-6)
    # and by what each thread was in while the gap lasted: the second gap
    # is 2000 ns of runner.next, 3000 of runner.run around it, 500 of
    # runner.d2h on one thread, 500 of runner.coerce on the other
    assert found["in_spans"] == {
        "runner.run": pytest.approx(3.5e-6),
        "runner.next": pytest.approx(2e-6),
        "runner.coerce": pytest.approx(0.5e-6),
        "runner.d2h": pytest.approx(3e-6)}


def test_innermost_follows_nesting_and_the_gaps_between_spans():
    times, names = idle_gaps.innermost(
        [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 200, 300)])
    assert list(zip(times, names)) == [
        (0, "a"), (10, "b"), (20, "c"), (30, "b"), (40, "a"), (100, None),
        (200, "d"), (300, None)]


def test_the_two_transform_shares_add_up_to_the_idle_share(profile, found,
                                                           monkeypatch):
    reduced = trace_reduce.reduce_profile(profile, window_s=16e-6)
    monkeypatch.setattr(idle_gaps, "analysis", lambda trace, counters: found)
    values = {}
    for name in ("idle_in_pass_pct.transform",
                 "idle_between_passes_pct.transform",
                 "device_idle_pct.transform"):
        values[name] = run.load_by_path("layer_metrics", name).read(
            reduced, {}, {}, {}, None)
    # inside runner.run [500, 10000]: 500 of the first gap, 5000 of the
    # second, of 16000 traced
    assert values["idle_in_pass_pct.transform"] == pytest.approx(
        100 * 5500 / 16000)
    # outside it, counted on its own: 500 of the first gap, 1000 of the
    # second, the third whole
    assert values["idle_between_passes_pct.transform"] == pytest.approx(
        100 * 5500 / 16000)
    assert values["idle_in_pass_pct.transform"] \
        + values["idle_between_passes_pct.transform"] \
        == pytest.approx(values["device_idle_pct.transform"])
    # a stretch the two readings of the trace disagree on shows as a
    # residual, not inside the share between passes
    longer = dict(reduced, window_s=20e-6)
    shares = [run.load_by_path("layer_metrics", name).read(
        longer, {}, {}, {}, None) for name in values]
    assert shares[0] + shares[1] == pytest.approx(100 * 11000 / 20000)
    assert shares[2] == pytest.approx(100 * 15000 / 20000)


@pytest.mark.parametrize("named", [True, False])
def test_the_last_line_carries_the_named_gaps(found, monkeypatch, named):
    """``run.py`` puts the analysis' ``top`` into ``breakdown.idle_gaps``:
    ``[span name, seconds]`` pairs, ``device_ops``' shape; ``[]`` only where
    there is no analysis."""
    monkeypatch.setattr(idle_gaps, "analysis",
                        lambda trace, counters: found if named else None)
    line, _ = tiny.run_cell("resnet50_transform_resident", trace=1)
    gaps = line["breakdown"]["idle_gaps"]
    assert gaps == (found["top"] if named else [])
    if named:
        assert 0 < len(gaps) <= 10 and sorted(n for n, _ in gaps) == [
            "runner.coerce", "runner.d2h", "runner.next", "unattributed"]
        assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                              reverse=True)


def test_span_log_spans_are_laid_over_a_device_only_trace(monkeypatch):
    """``run.py`` traces with the host tracer off: the spans come from the
    program's log, on the wall clock of ``profile_start_time``."""
    ms = 1_000_000
    device_only = '''
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 9000000000 }
    events { metadata_id: 1 offset_ps: 30000000000 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 1700000000000000000 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
}
'''
    # the device is busy [1, 10] and [30, 39] ms of the trace
    log = [("runner.run", 7, ORIGIN + 0 * ms, ORIGIN + 12 * ms),
           ("runner.dispatch", 7, ORIGIN + 1 * ms, ORIGIN + 2 * ms),
           ("runner.d2h", 7, ORIGIN + 12 * ms, ORIGIN + 13 * ms),
           ("runner.run", 7, ORIGIN + 29 * ms, ORIGIN + 40 * ms),
           ("runner.dispatch", 7, ORIGIN + 30 * ms, ORIGIN + 31 * ms)]
    from mmlspark_tpu.observability import tracing
    monkeypatch.setattr(tracing, "span_log", lambda: log)
    found = idle_gaps.analyse(
        jax.profiler.ProfileData.from_text_proto(device_only),
        [ORIGIN, ORIGIN + 40 * ms])
    assert found["gaps"] == [[(0, 1 * ms), (10 * ms, 30 * ms),
                              (39 * ms, 40 * ms)]]
    # 2 ms of the long gap are inside the first runner.run, 1 ms inside the
    # second; the first gap and the last are inside a pass too
    assert idle_gaps.in_pass_seconds(found) \
        == pytest.approx(5e-3)
    assert found["by_name"]["runner.run"] == pytest.approx(22e-3)
    # by where the thread was meanwhile: the dispatches ran while the
    # device was busy, 13-29 ms no span was open
    assert found["in_spans"] == {"runner.run": pytest.approx(5e-3),
                                 "runner.d2h": pytest.approx(1e-3)}
    assert idle_gaps.between_passes_seconds(found) == pytest.approx(17e-3)
    # the run's clock check: the one gap that drained the device ends at
    # 30 ms, the first launching span since its start opened at 30 ms
    # (until 31 ms)
    assert found["diagnostics"]["first_launch_ms"] == dict(
        n=1, after_span_start=[0.0, 0.0, 0.0],
        after_span_end=[-1.0, -1.0, -1.0])
    assert found["diagnostics"]["idle_named_share"] == pytest.approx(22 / 22)


def test_a_device_operation_before_its_launch_reads_as_negative():
    ms = 1_000_000
    threads = {1: [("runner.coerce", 101 * ms, 104 * ms)],
               2: [("runner.dispatch", 105 * ms, 106 * ms),
                   ("runner.d2h", 60 * ms, 70 * ms)]}
    # the first gap is too short to have drained the device; the second
    # ends 1.2 ms before the span that launched what ended it
    gaps = [(10 * ms, 12 * ms), (50 * ms, 99.8 * ms)]
    assert idle_gaps.first_launches(gaps, threads) \
        == [(pytest.approx(-1.2), pytest.approx(-4.2))]


def test_engine_timelines_of_the_window_or_nothing(monkeypatch):
    from benchmarks.layer_metrics import _timeline
    from mmlspark_tpu.serving import generation
    rows = [dict(submitted_at=t, admitted_at=t + 0.001,
                 first_token_at=t + 0.1 + 0.01 * i, finished_at=t + 5)
            for i, t in enumerate((9.0, 10.0, 20.0, 30.0, 61.0))]
    monkeypatch.setattr(generation, "recent_timelines", lambda: rows)
    counters = dict(t0=10.0, t1=61.0, ttft=[0.16, 0.17, 0.18])
    assert [a["submitted_at"] for a in _timeline.window_requests(counters)] \
        == [10.0, 20.0, 30.0]
    p95 = run.load_by_path("layer_metrics", "engine_ttft_p95_ms.generate")
    wait = run.load_by_path("layer_metrics", "queue_wait_p95_ms.generate")
    assert p95.read({}, counters, {}, {}, None) == pytest.approx(130.0)
    assert wait.read({}, counters, {}, {}, None) == pytest.approx(1.0)
    assert _timeline.against_client(counters) == dict(
        requests_engine=3, requests_client=3,
        client_minus_engine_ttft_ms=dict(
            min=pytest.approx(50.0), median=pytest.approx(50.0),
            max=pytest.approx(50.0)))
    # a full list may have dropped a request of the window: no percentile
    monkeypatch.setattr(generation, "RECENT_TIMELINES", len(rows))
    assert _timeline.window_requests(counters) is None
    assert p95.read({}, counters, {}, {}, None) is None
    # and a program that keeps no such list (the parent) reads as nothing
    monkeypatch.delattr(generation, "recent_timelines")
    assert wait.read({}, counters, {}, {}, None) is None


def test_a_program_with_no_span_log_reads_as_nothing(monkeypatch, profile):
    from mmlspark_tpu.observability import tracing
    monkeypatch.delattr(tracing, "span_log")
    assert idle_gaps.program_spans(ORIGIN) == {}
    device_only = jax.profiler.ProfileData.from_text_proto(
        TWO_THREADS[:TWO_THREADS.index('planes {\n  name: "/host:CPU"')]
        + TWO_THREADS[TWO_THREADS.index('planes {\n  name: "Task Env'):])
    assert idle_gaps.analyse(device_only, [ORIGIN, ORIGIN + 16000]) is None
