"""Device residency: the one-h2d/one-d2h pipeline contract, LRU spill,
staging slabs, and the serving/runner integration points.

Every transfer assertion reads the ``mmlspark_residency_*`` counters — the
same numbers bench.py embeds — so these tests pin the *accounting* as well
as the behavior."""

import numpy as np
import pytest

import mmlspark_tpu.core.residency as R
from mmlspark_tpu.core import DataFrame, Pipeline, concat
from mmlspark_tpu.core import schema as S
from mmlspark_tpu.core.pipeline import DeviceTransformer
from mmlspark_tpu.core.residency import (DeviceColumn, HostMirror,
                                         configure_residency,
                                         get_residency_manager,
                                         residency_stats)
from mmlspark_tpu.models.runner import StagingSlabPool
from mmlspark_tpu.observability import reset_all
from mmlspark_tpu.ops.padding import pad_axis_device


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    # a manager of this file's own for the test's duration, unbudgeted unless
    # a test configures otherwise, and the counters at zero. The process-wide
    # manager also counts what is not this file's to drop: the reservation of
    # a PagedKVPool that another file's engine still holds when xdist deals
    # both files to one worker counts against any budget set here.
    monkeypatch.setattr(R, "_MANAGER", R.ResidencyManager(0))
    reset_all()


#: the manager the process's other owners reserve in
PROCESS_WIDE = R.get_residency_manager()


@pytest.fixture(scope="module")
def live_pool():
    """A page pool alive in the process-wide manager, as an engine of an
    earlier test file is."""
    from mmlspark_tpu.models.zoo.transformer import DECODER_MINI
    from mmlspark_tpu.serving.kv_pool import PagedKVPool
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(R, "_MANAGER", PROCESS_WIDE)
        pool = PagedKVPool(DECODER_MINI, num_pages=2, page_size=8)
    assert PROCESS_WIDE.reserved_bytes() >= pool.device_bytes() > 32
    yield pool
    pool.close()


@pytest.fixture(params=["alone", "beside_live_pool"])
def beside(request):
    """The spill tests run alone and beside a live pool of another owner."""
    if request.param == "beside_live_pool":
        request.getfixturevalue("live_pool")


def _h2d(site):
    return R.M_H2D.labels(site=site).get()


def _d2h(site):
    return R.M_D2H.labels(site=site).get()


class Scale(DeviceTransformer):
    def _transform_device(self, arrays):
        return {n: a * 2.0 for n, a in arrays.items()}


# ---------------------------------------------------------------------------
# the tentpole contract: one h2d at ingest, one d2h at the sink


def test_three_stage_pipeline_moves_data_exactly_twice():
    df = DataFrame({"x": np.arange(8, dtype=np.float32)})
    model = Pipeline(stages=[Scale(input_cols=["x"]),
                             Scale(input_cols=["x"]),
                             Scale(input_cols=["x"])]).fit(df)
    reset_all()   # fit's pass-through transforms staged their own copy
    out = model.transform(df)

    # stage 1 staged the column (one miss, one ingest transfer op);
    # stages 2 and 3 found it resident (hits, zero transfers)
    assert _h2d("ingest") == 1
    assert _h2d("restage") == 0
    assert R.M_MISSES.labels().get() == 1
    assert R.M_HITS.labels().get() == 2
    assert _d2h("sink") == 0     # nothing has left the device yet

    host = out.to_host()
    assert _d2h("sink") == 1     # ONE batched fetch at the sink
    assert _d2h("materialize") == 0
    np.testing.assert_allclose(host["x"], np.arange(8) * 8.0)

    stats = residency_stats()
    assert stats["residency_hit_rate"] == pytest.approx(2 / 3)


def test_device_put_is_idempotent():
    df = DataFrame({"x": np.arange(4, dtype=np.float32)})
    staged = df.device_put(["x"])
    again = staged.device_put(["x"])
    assert again.is_resident("x")
    assert _h2d("ingest") == 1
    assert R.M_HITS.labels().get() == 1
    assert R.M_MISSES.labels().get() == 1


def test_row_ops_stay_resident_and_keep_metadata():
    df = DataFrame({"x": np.arange(12, dtype=np.float32)}, npartitions=3)
    df = S.set_categorical_metadata(df, "x", ["lo", "hi"])
    df = df.device_put(["x"])

    out = (df.filter(np.arange(12) % 2 == 0)
             .take([0, 2, 4])
             .sort_values("x", ascending=False)
             .repartition(2)
             .head(2))
    assert out.is_resident("x")
    assert S.get_categorical_levels(out, "x") == ["lo", "hi"]
    # the whole chain ran on device: still the single ingest transfer,
    # nothing pulled back to host
    assert _h2d("ingest") == 1
    assert _d2h("sink") == 0 and _d2h("materialize") == 0
    # evens -> take rows 0/2/4 of them ([0, 4, 8]) -> sorted descending
    np.testing.assert_allclose(out.to_host()["x"], [8.0, 4.0])


def test_concat_of_resident_frames_stays_resident():
    df = DataFrame({"x": np.arange(6, dtype=np.float32)},
                   npartitions=2).device_put(["x"])
    parts = list(df.partitions())
    back = concat(parts)
    assert back.is_resident("x")
    assert _d2h("sink") == 0 and _d2h("materialize") == 0
    np.testing.assert_allclose(back.to_host()["x"], np.arange(6))


# ---------------------------------------------------------------------------
# LRU spill under a device-memory budget


def test_lru_spill_respects_budget_and_restages_on_access(beside):
    df = DataFrame({"x": np.zeros(16, dtype=np.float32)}, npartitions=4)
    df = df.device_put(["x"])        # 4 chunks x 16 bytes
    col = df.device_column("x")
    assert col.chunk_states() == ["device"] * 4

    configure_residency(32)          # room for 2 of the 4 chunks
    assert col.chunk_states() == ["spilled", "spilled", "device", "device"]
    stats = get_residency_manager().stats()
    assert stats["resident_bytes"] <= 32
    assert R.M_SPILLS.labels().get() == 2
    # ingest-staged chunks kept their host view — spilling them is free
    assert _d2h("spill") == 0

    # touching the column restages the spilled chunks (counted) and the
    # data survives the round trip
    assert len(col.device_array()) == 16
    assert _h2d("restage") > 0


def test_spill_is_lru_ordered(beside):
    df = DataFrame({"x": np.zeros(16, dtype=np.float32)}, npartitions=4)
    df = df.device_put(["x"])
    col = df.device_column("x")
    # touch chunk 0 so it is most-recently-used before the squeeze
    col.slice_rows(0, 4).device_array()
    configure_residency(32)
    states = col.chunk_states()
    assert states[0] == "device"     # recently used: survived
    assert states.count("spilled") == 2


# ---------------------------------------------------------------------------
# HostMirror: device-born columns materialize lazily, once, counted


def test_host_mirror_materializes_once_and_is_counted():
    import jax.numpy as jnp
    df = DataFrame({"x": np.arange(4, dtype=np.float32)})
    df = df.with_device_column("y", jnp.arange(4, dtype=jnp.float32) + 1)
    assert df.is_resident("y")
    assert _d2h("materialize") == 0  # shape/dtype queries are free

    first = df["y"]
    assert _d2h("materialize") == 1
    assert R.M_MATERIALIZE.labels(op="materialize").get() == 1
    np.testing.assert_allclose(first, [1, 2, 3, 4])
    df["y"]                          # cached: no second transfer
    assert _d2h("materialize") == 1


def test_to_host_returns_plain_frame():
    df = DataFrame({"x": np.arange(4, dtype=np.float32)}).device_put(["x"])
    host = df.to_host()
    assert not host.resident_columns
    assert isinstance(host["x"], np.ndarray)


# ---------------------------------------------------------------------------
# serving: already-resident inputs are not re-staged


def test_serving_stage_ingest_skips_resident_input():
    from mmlspark_tpu.serving.engine import ServingEngine
    eng = ServingEngine(transform_fn=lambda df: df,
                        schema={"x": float}, device_ingest=["x"])
    try:
        parsed = DataFrame({"x": np.arange(4, dtype=np.float32)})
        staged = eng._stage_ingest(parsed)
        assert staged.is_resident("x")
        assert _h2d("ingest") == 1 and R.M_MISSES.labels().get() == 1

        again = eng._stage_ingest(staged)
        assert again.is_resident("x")
        assert _h2d("ingest") == 1          # no re-stage
        assert R.M_HITS.labels().get() == 1
    finally:
        eng.server.close()


# ---------------------------------------------------------------------------
# runner integration: resident columns feed device slices, zero h2d payload


def test_jax_model_feeds_resident_column_without_host_roundtrip():
    from mmlspark_tpu.models.jax_model import JaxModel
    m = JaxModel(apply_fn=lambda p, f: {"y": f["input"] * 3.0},
                 feed_dict={"input": "x"}, mini_batch_size=4,
                 prefetch_depth=0)
    df = DataFrame({"x": np.arange(8, dtype=np.float32)}).device_put(["x"])
    out = m.transform(df)
    np.testing.assert_allclose(out["y"], np.arange(8) * 3.0)
    # the runner counted one residency hit per device-fed batch and moved
    # zero payload bytes over the h2d stage
    assert R.M_HITS.labels().get() >= 2      # 8 rows / 4 per batch
    assert m.stage_counters.snapshot()["h2d"]["bytes"] == 0


# ---------------------------------------------------------------------------
# staging slabs + device padding


def test_staging_slab_pool_reuses_and_caps():
    pool = StagingSlabPool(depth=2)
    a = pool.acquire((4, 2), np.float32)
    b = pool.acquire((4, 2), np.float32)
    assert pool.stats()["allocs"] == 2
    pool.release(a)
    c = pool.acquire((4, 2), np.float32)
    assert c is a and pool.stats()["reuses"] == 1
    # foreign arrays are ignored, issued slabs recirculate at most `depth`
    assert not pool.release(np.zeros((4, 2), np.float32))
    for arr in (b, c):
        assert pool.release(arr)
    assert not pool.release(c)               # double release is a no-op


def test_pad_axis_device_stays_on_device():
    import jax
    arr = jax.device_put(np.arange(6, dtype=np.float32))
    padded = pad_axis_device(arr, 8)
    assert R.is_device_array(padded)
    assert padded.shape == (8,)
    np.testing.assert_allclose(np.asarray(padded)[6:], 0.0)
    assert pad_axis_device(arr, 6) is arr    # already at bucket: no-op


def test_device_column_transfer_batching():
    # a multi-partition ingest is ONE transfer op; a multi-chunk sink
    # fetch is ONE transfer op — the batched-put/get accounting bench
    # reports depends on this
    df = DataFrame({"x": np.arange(12, dtype=np.float32)}, npartitions=3)
    df = df.device_put(["x"])
    assert _h2d("ingest") == 1
    col = df.device_column("x")
    assert len(col.chunk_states()) == 3
    col.to_host()
    # ingest kept host views, so the sink fetch is free (no host-less
    # chunks); a device-born column pays exactly one
    dcol = DeviceColumn.from_device(
        [c * 1.0 for c in col.device_chunks()])
    dcol.to_host()
    assert _d2h("sink") == 1
