"""A pass's outputs are written once, where the frame holds them
(``models/runner.py`` ``FrameOutputs`` / ``collect``, shared by ``ONNXModel``
and ``JaxModel``): bit for bit what joining the drained batches and casting
the joined array gave (the reference below is that older path, kept here),
and ``concat`` copies none of it."""

import threading

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.models import jax_model, onnx_model, runner
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.onnx_model import ONNXModel
from mmlspark_tpu.observability import tracing as tr

DIN, DOUT = 8, 5


def joined_then_cast(batches, names, int64=()):
    """The reference: ``np.concatenate`` of a partition's batches with the
    padding cut, then ``astype``, a column at a time."""
    cols = {}
    for name in names:
        arr = np.concatenate([outs[name][:b] for outs, b in batches])
        if arr.dtype == jnp.bfloat16:
            arr = arr.astype(np.float32)
        if name in int64:
            arr = arr.astype(np.int64)
        cols[name] = arr
    return cols


def _weights(seed=0):
    return np.random.default_rng(seed).normal(
        0, 0.5, (DIN, DOUT)).astype(np.float32)


def _onnx(compute_dtype="float32", **kw):
    import mmlspark_tpu.onnx as O
    graph = O.make_graph(
        [O.make_node("MatMul", ["x", "w"], ["logits"])], "linear",
        inputs=[O.make_tensor_value_info("x", np.float32, ["N", DIN])],
        outputs=[O.make_tensor_value_info("logits", np.float32, ["N", DOUT])],
        initializers={"w": _weights()})
    return ONNXModel(O.make_model(graph), feed_dict={"x": "feats"},
                     fetch_dict={"logits": "logits"}, pin_devices=False,
                     mini_batch_size=4, compute_dtype=compute_dtype, **kw)


def _linear(params, feeds):
    return {"logits": feeds["input"] @ params["w"]}


def _jax(compute_dtype="float32", **kw):
    return JaxModel(_linear, {"w": _weights()}, feed_dict={"input": "feats"},
                    pin_devices=False, mini_batch_size=4,
                    compute_dtype=compute_dtype, **kw)


MODELS = {"onnx": (_onnx, onnx_model), "jax": (_jax, jax_model)}


def _frame(rows=37, seed=1, **kw):
    X = np.random.default_rng(seed).normal(0, 1, (rows, DIN)).astype(
        np.float32)
    return DataFrame({"feats": X}, **kw)


def _transform_recording(monkeypatch, module, model, df):
    """``model.transform(df)`` and ``{pidx: the batches its partition
    drained}``, as the stage handed them to ``collect``."""
    drained = {}

    def recording(batches, outputs, pidx, names=None):
        drained[pidx] = list(batches)
        return runner.collect(drained[pidx], outputs, pidx, names)

    monkeypatch.setattr(module, "collect", recording)
    return model.transform(df), drained


def _expected(drained, names, int64=()):
    parts = [joined_then_cast(drained[p], names, int64)
             for p in sorted(drained) if drained[p]]
    return {n: np.concatenate([p[n] for p in parts]) for n in names}


def _same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


# rows, frame keywords: a ragged last batch in every partition (the padding
# cut); rows no multiple of the partitions; a partition without rows; a
# frame of one partition; fewer rows than a batch
FRAMES = {
    "ragged_p4": (37, dict(npartitions=4)),
    "indivisible_p3": (26, dict(npartitions=3)),
    "empty_partition": (37, dict(partition_sizes=[10, 0, 17, 10])),
    "empty_first_partition": (14, dict(partition_sizes=[0, 9, 5])),
    "one_partition": (9, dict(npartitions=1)),
    "under_a_batch": (3, dict(npartitions=2)),
}


@pytest.mark.parametrize("frame", FRAMES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", MODELS)
def test_outputs_equal_the_joined_batches_bit_for_bit(
        monkeypatch, kind, dtype, frame):
    make, module = MODELS[kind]
    rows, kw = FRAMES[frame]
    df = _frame(rows, **kw)
    out, drained = _transform_recording(monkeypatch, module, make(dtype), df)
    wire = {outs["logits"].dtype for b in drained.values() for outs, _ in b}
    assert wire == {np.dtype(jnp.bfloat16 if dtype == "bfloat16"
                             else np.float32)}
    got = out["logits"]
    _same_bits(got, _expected(drained, ["logits"])["logits"])
    assert got.dtype == np.float32 and got.flags.c_contiguous
    assert len(got) == rows
    assert [len(p) for p in out.partitions()] \
        == [hi - lo for lo, hi in df.partition_bounds()]
    assert np.shares_memory(out["feats"], df["feats"])    # passed through


def test_an_argmax_column_comes_out_int64(monkeypatch):
    model = _onnx("bfloat16", argmax_dict={"pred": "logits"})
    out, drained = _transform_recording(
        monkeypatch, onnx_model, model, _frame(npartitions=4))
    want = _expected(drained, ["logits", "pred"], int64={"pred"})
    assert {outs["pred"].dtype for b in drained.values()
            for outs, _ in b} == {np.dtype(np.int32)}
    _same_bits(out["pred"], want["pred"])
    _same_bits(out["logits"], want["logits"])
    assert out["pred"].dtype == np.int64
    assert np.array_equal(out["pred"], out["logits"].argmax(axis=1))


def test_a_host_fallback_softmax_reads_the_joined_column():
    # "probs" of a column the graph does not hand out itself: ``_transform``
    # computes it on the host from the frame-wide array
    model = _onnx(softmax_dict={"probs": "logits"})
    model._ensure_jitted()
    model._fused_cols = set()
    model._out_col_names = ["logits"]
    out = model.transform(_frame(npartitions=4))
    logits = out["logits"]
    e = np.exp(logits - logits.max(axis=-1, keepdims=True))
    np.testing.assert_allclose(out["probs"], e / e.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert len(out["probs"]) == 37


def test_outputs_left_on_the_device_take_their_own_branch(monkeypatch):
    def unused(*a, **k):
        raise AssertionError("output_device collects nothing on the host")

    df = _frame(npartitions=4)
    want = _onnx().transform(df)["logits"]
    monkeypatch.setattr(onnx_model, "collect", unused)
    out = _onnx(output_device=True).transform(df)
    assert out.is_resident("logits")
    _same_bits(np.asarray(out["logits"]), want)


@pytest.mark.parametrize("kind", MODELS)
def test_two_transforms_at_once_hold_their_own_buffers(kind):
    model = MODELS[kind][0]()
    frames = [_frame(41, seed=s, npartitions=4) for s in (1, 2)]
    alone = [model.transform(df)["logits"].copy() for df in frames]
    got = [None, None]
    start = threading.Barrier(2)

    def call(i):
        start.wait(timeout=60)
        for _ in range(5):
            got[i] = model.transform(frames[i])["logits"]

    threads = [threading.Thread(target=call, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    for mine, want in zip(got, alone):
        _same_bits(mine, want)
    assert not np.shares_memory(got[0], got[1])


@pytest.mark.parametrize("kind", MODELS)
def test_concat_copies_nothing_of_a_four_partition_pass(kind):
    model = MODELS[kind][0]("bfloat16")
    df = _frame(npartitions=4)
    root = tr.start_trace("pass")
    with tr.activate(root):
        out = model.transform(df)
    root.end()
    span, = [s for s in root.trace.spans if s.name == "frame.concat"]
    assert span.attrs["parts"] == 4
    assert span.attrs["bytes_copied"] == 0
    assert span.attrs["bytes_viewed"] \
        == out["logits"].nbytes + df["feats"].nbytes
    # ONE array a column: the partitions' results are its row ranges
    parts = list(out.partitions())
    assert all(np.shares_memory(p["logits"], out["logits"]) for p in parts)
    assert out["logits"].flags.c_contiguous


def test_a_batch_of_another_row_shape_is_refused():
    outputs = runner.FrameOutputs([(0, 8)])
    outputs.write("y", 0, np.zeros((4, 3), np.float32))
    with pytest.raises(ValueError, match="row shape"):
        outputs.write("y", 4, np.zeros((4, 1), np.float32))


def test_the_two_stages_share_one_collection():
    assert onnx_model.collect is runner.collect is jax_model.collect
    assert onnx_model.FrameOutputs is runner.FrameOutputs \
        is jax_model.FrameOutputs


def _runner(n=10, **kw):
    import jax
    data = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    return runner.BatchRunner(
        jax.jit(lambda params, feeds: {"y": feeds["x"] * params["w"]}),
        {"w": jnp.float32(2.0)}, coerce=lambda sl: {"x": data[sl]},
        put=jax.device_put, mini_batch_size=4, **kw), data


@pytest.mark.parametrize("rows", [10, 8, 0])
def test_a_batch_at_a_time_is_what_one_fetch_gives_counted_once(rows):
    """``drain_each``: ``drain``'s batches in order, one ``d2h`` count and
    one ``runner.d2h`` span a batch; a reader that stops early still
    accounts for what it fetched."""
    whole, data = _runner(rows)
    each, _ = _runner(rows)
    want = whole.drain(whole.run(rows))
    tr._SPAN_LOG.clear()
    got = list(each.drain_each(each.run(rows)))
    assert [b for _, b in got] == [b for _, b in want]
    for (g, _), (w, _) in zip(got, want):
        _same_bits(g["y"], w["y"])
    assert [name for name, *_ in tr.span_log()].count("runner.d2h") \
        == len(want)
    counted = each.counters.snapshot().get("d2h", {"calls": 0, "bytes": 0})
    once = whole.counters.snapshot().get("d2h", {"calls": 0, "bytes": 0})
    assert counted["calls"] == once["calls"] == (1 if rows else 0)
    assert counted["bytes"] == once["bytes"]
    if rows:
        early, _ = _runner(rows)
        reader = early.drain_each(early.run(rows))
        first, b = next(reader)
        reader.close()
        assert early.counters.snapshot()["d2h"]["bytes"] == first["y"].nbytes
