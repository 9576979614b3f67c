"""The trace reduction on a small synthetic xplane."""

import jax
import pytest

from benchmarks import trace_reduce

XSPACE = '''
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 1000000 }
  }
  lines {
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11000000 }
  }
  lines {
    name: "Steps"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 90000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.1" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(123)" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.12" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "host work" } }
}
'''


@pytest.fixture(scope="module")
def reduced():
    profile = jax.profiler.ProfileData.from_text_proto(XSPACE)
    return trace_reduce.reduce_profile(profile, window_s=20e-6)


def test_busy_is_the_union_of_device_operations(reduced):
    # [1000, 3000] u [2000, 5000] u [11000, 12000] ns = 4000 + 1000 ns;
    # the Steps line and the host plane are not work on the device
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(5e-6)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) \
        == pytest.approx(75.0)


def test_operations_sum_under_one_key_whatever_their_number(reduced):
    assert reduced["ops"]["fusion"] == [pytest.approx(3e-6), 2]
    assert reduced["ops"]["all-reduce"] == [pytest.approx(3e-6), 1]
    assert reduced["modules"]["jit_step"] == [pytest.approx(11e-6), 1]
    assert reduced["module_ops"]["jit_step/fusion"] \
        == [pytest.approx(3e-6), 2]
    assert trace_reduce.op_seconds(reduced, "^jit_step/all-reduce$",
                                   "module_ops") == (pytest.approx(3e-6), 1)
    assert trace_reduce.top_ops(reduced, 1)[0][0] in ("fusion", "all-reduce")
    assert trace_reduce.op_seconds(reduced, "^fusion$") \
        == (pytest.approx(3e-6), 2)


def test_collective_time_and_its_exposed_part(reduced):
    # the all-reduce runs [2000, 5000]; compute covers [1000, 3000] of it
    assert reduced["collective_s"] == pytest.approx(3e-6)
    assert reduced["collective_exposed_s"] == pytest.approx(2e-6)


@pytest.mark.parametrize("intervals,seconds", [
    ([], 0.0),
    ([(0, 10)], 10e-9),
    ([(0, 10), (5, 7)], 10e-9),
    ([(0, 10), (10, 20), (30, 31)], 21e-9),
])
def test_union_seconds(intervals, seconds):
    assert trace_reduce.union_seconds(intervals) == pytest.approx(seconds)


def test_window_is_never_shorter_than_the_device_events_span():
    profile = jax.profiler.ProfileData.from_text_proto(XSPACE)
    short = trace_reduce.reduce_profile(profile, window_s=1e-6)
    assert short["window_s"] == pytest.approx(11e-6)    # 1000 .. 12000 ns
    assert short["busy_s"] <= short["window_s"]


def test_a_trace_with_no_device_plane_reads_as_no_device(reduced):
    host_only = jax.profiler.ProfileData.from_text_proto(
        XSPACE[XSPACE.index('planes {\n  name: "/host:CPU"'):])
    out = trace_reduce.reduce_profile(host_only, 1.0)
    assert out["devices"] == 0 and out["busy_s"] == 0.0
