import numpy as np
import pytest

from mmlspark_tpu.core import (ComplexParam, DataFrame, Estimator, Model,
                               Param, Pipeline, PipelineModel, PipelineStage,
                               Transformer, concat)
from mmlspark_tpu.core import schema as S


class AddConst(Transformer):
    input_col = Param(str, default="x", doc="in")
    output_col = Param(str, default="y", doc="out")
    amount = Param(float, default=1.0, doc="value to add")

    def _transform(self, df):
        return df.with_column(self.output_col, df[self.input_col] + self.amount)


class MeanCenter(Estimator):
    input_col = Param(str, default="x", doc="in")

    def _fit(self, df):
        return MeanCenterModel(mean=float(np.mean(df[self.input_col])),
                               input_col=self.input_col)


class MeanCenterModel(Model):
    input_col = Param(str, default="x", doc="in")
    mean = Param(float, default=0.0, doc="fitted mean")

    def _transform(self, df):
        return df.with_column(self.input_col, df[self.input_col] - self.mean)


class TestParams:
    def test_defaults_and_set(self):
        t = AddConst()
        assert t.amount == 1.0
        t.set(amount=2)
        assert t.amount == 2.0
        t.amount = 3.5
        assert t.get("amount") == 3.5

    def test_constructor_kwargs(self):
        t = AddConst(amount=5, input_col="a")
        assert t.amount == 5.0 and t.input_col == "a"

    def test_type_errors(self):
        with pytest.raises(TypeError):
            AddConst(amount="nope")
        with pytest.raises(KeyError):
            AddConst(bogus=1)

    def test_copy_isolated(self):
        t = AddConst(amount=1)
        t2 = t.copy({"amount": 9})
        assert t.amount == 1.0 and t2.amount == 9.0

    def test_explain(self):
        assert "value to add" in AddConst().explain_params()

    def test_uids_unique(self):
        assert AddConst().uid != AddConst().uid


class TestDataFrame:
    def test_basic(self):
        df = DataFrame({"x": [1.0, 2.0, 3.0], "s": ["a", "b", "c"]}, npartitions=2)
        assert len(df) == 3
        assert df.columns == ["x", "s"]
        assert df["s"].dtype == object
        assert df.schema()["x"] == "float64"

    def test_partitions(self):
        df = DataFrame({"x": np.arange(10)}, npartitions=3)
        parts = list(df.partitions())
        assert [len(p) for p in parts] == [4, 3, 3]
        assert np.array_equal(concat(parts)["x"], np.arange(10))

    def test_map_partitions(self):
        df = DataFrame({"x": np.arange(10, dtype=np.float64)}, npartitions=4)
        out = df.map_partitions(lambda p, i: p.with_column("pid", np.full(len(p), i)))
        assert len(out) == 10
        assert sorted(set(out["pid"])) == [0, 1, 2, 3]

    def test_map_partitions_runs_concurrently(self):
        # partitions must overlap in time — this is what makes round-robin
        # chip pinning actually use k chips at once. Asserted via an
        # in-flight counter (robust to machine load, unlike wall-clock).
        import threading
        import time
        df = DataFrame({"x": np.arange(8, dtype=np.float64)}, npartitions=4)
        lock = threading.Lock()
        state = {"cur": 0, "peak": 0}

        def slow(p, i):
            with lock:
                state["cur"] += 1
                state["peak"] = max(state["peak"], state["cur"])
            time.sleep(0.1)
            with lock:
                state["cur"] -= 1
            return p

        out = df.map_partitions(slow, max_workers=4)
        assert len(out) == 8
        assert state["peak"] >= 2, f"partitions never overlapped: {state}"

    def test_map_partitions_order_and_errors(self):
        df = DataFrame({"x": np.arange(12, dtype=np.int64)}, npartitions=3)
        out = df.map_partitions(lambda p, i: p)
        assert list(out["x"]) == list(range(12))  # partition order preserved
        import pytest
        with pytest.raises(ValueError, match="boom"):
            df.map_partitions(lambda p, i: (_ for _ in ()).throw(ValueError("boom")))
        # max_workers=1 forces the sequential path
        out = df.map_partitions(lambda p, i: p, max_workers=1)
        assert list(out["x"]) == list(range(12))

    def test_ops(self):
        df = DataFrame({"x": [1, 2, 3], "y": [4, 5, 6]})
        assert df.select(["y"]).columns == ["y"]
        assert df.drop("x").columns == ["y"]
        assert df.rename({"x": "z"}).columns == ["z", "y"]
        assert list(df.filter(np.array([True, False, True]))["x"]) == [1, 3]
        assert list(df.sort_values("x", ascending=False)["x"]) == [3, 2, 1]

    def test_pandas_roundtrip(self):
        import pandas as pd
        pdf = pd.DataFrame({"a": [1.5, 2.5], "b": ["x", "y"]})
        df = DataFrame.from_pandas(pdf, npartitions=2)
        back = df.to_pandas()
        assert list(back["a"]) == [1.5, 2.5]
        assert list(back["b"]) == ["x", "y"]

    def test_metadata_preserved(self):
        df = DataFrame({"x": [1, 2], "y": [3, 4]})
        df = S.set_categorical_metadata(df, "x", ["lo", "hi"])
        assert S.get_categorical_levels(df.select(["x"]), "x") == ["lo", "hi"]
        assert S.get_categorical_levels(df.with_column("z", [0, 0]), "x") == ["lo", "hi"]
        assert S.get_categorical_levels(df.rename({"x": "w"}), "w") == ["lo", "hi"]
        assert not S.is_categorical(df, "y")

    def test_metadata_survives_row_ops(self):
        # regression: the row-reshaping ops rebuild the frame — each must
        # carry column_metadata through, not silently drop it
        df = DataFrame({"x": [3, 1, 2], "y": [6, 4, 5]}, npartitions=2)
        df = S.set_categorical_metadata(df, "x", ["lo", "hi"])
        outs = {
            "filter": df.filter(np.array([True, False, True])),
            "take": df.take([0, 2]),
            "sort_values": df.sort_values("x"),
            "repartition": df.repartition(3),
            "head": df.head(2),
        }
        for op, out in outs.items():
            assert S.get_categorical_levels(out, "x") == ["lo", "hi"], op

    def test_unused_column_name(self):
        df = DataFrame({"x": [1], "x_1": [2]})
        assert S.find_unused_column_name("x", df) == "x_2"

    def test_assemble_vector(self):
        df = DataFrame({"a": [1.0, 2.0],
                        "v": [np.array([3.0, 4.0]), np.array([5.0, 6.0])]})
        X = S.assemble_vector(df, ["a", "v"])
        assert X.shape == (2, 3)
        assert list(X[1]) == [2.0, 5.0, 6.0]


def _concat_bytes():
    from mmlspark_tpu.core.dataframe import M_CONCAT_BYTES
    return {how: M_CONCAT_BYTES.labels(how=how).get()
            for how in ("copied", "viewed")}


_BASE = np.arange(48, dtype=np.float32).reshape(12, 4)
_OBJECTS = np.array([str(i) for i in range(12)], dtype=object)

#: (case, parts, whether the result may be a view of ``_BASE``)
CONCAT_PARTS = [
    ("adjacent", [_BASE[0:3], _BASE[3:8], _BASE[8:12]], True),
    ("adjacent_inside", [_BASE[2:5], _BASE[5:9]], True),
    ("adjacent_1d", [_BASE.reshape(-1)[0:10], _BASE.reshape(-1)[10:48]],
     True),
    ("views_of_a_view", [_BASE[2:][0:3], _BASE[2:][3:7]], True),
    ("empty_in_the_middle", [_BASE[0:3], _BASE[3:3], _BASE[3:12]], True),
    ("single", [_BASE[2:9]], True),
    ("single_strided", [_BASE[::2]], False),
    ("gap", [_BASE[0:3], _BASE[4:8]], False),
    ("overlap", [_BASE[0:4], _BASE[3:8]], False),
    ("swapped", [_BASE[3:8], _BASE[0:3]], False),
    ("two_bases", [_BASE[0:3], _BASE.copy()[3:8]], False),
    ("strided_rows", [_BASE[0:6:2], _BASE[6:12:2]], False),
    ("column_sliced", [_BASE[0:3, :2], _BASE[3:8, :2]], False),
    ("mixed_dtypes", [_BASE[0:3], _BASE[3:8].astype(np.float64)], False),
    ("object_column", [_OBJECTS[0:5], _OBJECTS[5:12]], False),
    ("all_empty", [_BASE[0:0], _BASE[5:5]], False),
]


class TestConcatViews:
    """``concat`` returns the covering view of parts that lie in order in
    one buffer and ``np.concatenate``'s copy of anything else."""

    @pytest.mark.parametrize("case,parts,viewed", CONCAT_PARTS,
                             ids=[c[0] for c in CONCAT_PARTS])
    def test_rule(self, case, parts, viewed):
        before = _concat_bytes()
        out = concat([DataFrame({"x": p}) for p in parts])["x"]
        want = np.concatenate(parts)
        assert out.dtype == want.dtype and out.shape == want.shape
        assert np.array_equal(out, want)
        assert out.flags.c_contiguous
        shares = bool(len(out)) and any(
            np.shares_memory(out, p) for p in parts)
        assert shares == viewed
        moved = {k: v - before[k] for k, v in _concat_bytes().items()}
        assert moved == {"viewed": want.nbytes * viewed,
                         "copied": want.nbytes * (not viewed)}

    def test_parts_of_other_row_widths_raise_as_before(self):
        with pytest.raises(ValueError):
            concat([DataFrame({"x": _BASE[0:3]}),
                    DataFrame({"x": _BASE[3:8, :2]})])

    def test_a_view_keeps_the_buffer_alive_and_its_writeability(self):
        base = np.arange(20.0).reshape(10, 2)
        base.setflags(write=False)
        out = concat([DataFrame({"x": base[0:4]}),
                      DataFrame({"x": base[4:10]})])["x"]
        want = base.copy()
        del base
        assert not out.flags.writeable
        assert np.array_equal(out, want)

    def test_frames_without_rows_are_passed_over(self):
        # a partition without rows cannot always know its outputs' shapes
        full = DataFrame({"x": _BASE[0:3], "y": np.arange(3)})
        none = DataFrame({"x": np.zeros((0,), np.float32)})
        out = concat([none, full, none])
        assert out.columns == ["x", "y"] and len(out) == 3
        assert np.array_equal(out["x"], _BASE[0:3])
        assert concat([none, none]).columns == ["x"]

    @pytest.mark.parametrize("sizes", [[5, 0, 4, 3], [1, 11], [12]],
                             ids=str)
    def test_uneven_partition_sizes_survive_map_partitions(self, sizes):
        df = DataFrame({"x": _BASE, "s": _OBJECTS}, partition_sizes=sizes)
        out = df.map_partitions(lambda p, i: p.with_column(
            "pid", np.full(len(p), i)))
        assert [len(p) for p in out.partitions()] == sizes
        assert np.shares_memory(out["x"], _BASE)      # passed through
        assert np.array_equal(out["x"], _BASE)
        assert list(out["s"]) == list(_OBJECTS)
        assert list(out["pid"]) == [i for i, n in enumerate(sizes)
                                    for _ in range(n)]

    def test_span_and_counter_add_up_to_the_columns_bytes(self):
        from mmlspark_tpu.observability import tracing as tr
        df = DataFrame({"x": _BASE, "s": _OBJECTS}, npartitions=3)
        before = _concat_bytes()
        root = tr.start_trace("frame")
        with tr.activate(root):
            out = df.map_partitions(lambda p, i: p.with_column(
                "y", p["x"] * 2))
        root.end()
        span, = [s for s in root.trace.spans if s.name == "frame.concat"]
        moved = {k: v - before[k] for k, v in _concat_bytes().items()}
        assert span.attrs["bytes_viewed"] == moved["viewed"] == _BASE.nbytes
        assert span.attrs["bytes_copied"] == moved["copied"] \
            == out["y"].nbytes + _OBJECTS.nbytes
        assert span.attrs["parts"] == 3


class TestPipeline:
    def test_fit_transform(self):
        df = DataFrame({"x": [1.0, 2.0, 3.0]})
        pipe = Pipeline([MeanCenter(), AddConst(amount=10)])
        model = pipe.fit(df)
        out = model.transform(df)
        assert np.allclose(out["y"], [9.0, 10.0, 11.0])

    def test_transform_params_override(self):
        df = DataFrame({"x": [0.0]})
        out = AddConst().transform(df, {"amount": 7.0})
        assert out["y"][0] == 7.0


class TestSerialization:
    def test_transformer_roundtrip(self, tmp_save):
        t = AddConst(amount=3.25, output_col="zz")
        t.save(tmp_save)
        t2 = PipelineStage.load(tmp_save)
        assert isinstance(t2, AddConst)
        assert t2.amount == 3.25 and t2.output_col == "zz"
        assert t2.uid == t.uid

    def test_pipeline_model_roundtrip(self, tmp_save):
        df = DataFrame({"x": [1.0, 2.0, 3.0]})
        model = Pipeline([MeanCenter(), AddConst(amount=10)]).fit(df)
        model.save(tmp_save)
        model2 = PipelineModel.load(tmp_save)
        out1, out2 = model.transform(df), model2.transform(df)
        assert np.allclose(out1["y"], out2["y"])

    def test_complex_values(self, tmp_save):
        from mmlspark_tpu.core import serialize

        class Holder(Transformer):
            payload = ComplexParam(doc="arbitrary blob")

            def _transform(self, df):
                return df

        h = Holder()
        h.set(payload={"w": np.arange(6).reshape(2, 3).astype(np.float32),
                       "b": [np.ones(3), 2.0]})
        h.save(tmp_save)
        # class lives in a test function namespace → patch resolution
        loaded_meta_cls = serialize._resolve_class
        try:
            serialize._resolve_class = lambda p: Holder
            h2 = PipelineStage.load(tmp_save)
        finally:
            serialize._resolve_class = loaded_meta_cls
        p = h2.get("payload")
        assert np.array_equal(p["w"], h.get("payload")["w"])
        assert p["b"][1] == 2.0


def test_string_array_dtype_roundtrip(tmp_path):
    """'U'-dtype ndarrays keep their dtype through save/load (ADVICE r1)."""
    from mmlspark_tpu.core.serialize import load_value, save_value
    arr = np.array(["abc", "de", "f"])
    assert arr.dtype.kind == "U"
    p = str(tmp_path / "val")
    import os
    os.makedirs(p, exist_ok=True)
    tag = save_value({"labels": arr, "w": np.ones(2)}, p)
    back = load_value(tag, p)
    assert back["labels"].dtype == arr.dtype
    assert list(back["labels"]) == list(arr)


class TestSharedPartitionPool:
    def test_pool_reused_across_calls(self):
        from mmlspark_tpu.core import dataframe as dfmod
        a = dfmod._shared_pool(4)
        b = dfmod._shared_pool(4)
        assert a is b
        assert dfmod._shared_pool(2) is not a

    def test_map_partitions_unchanged_semantics(self):
        df = DataFrame({"x": np.arange(20)}, npartitions=4)
        out = df.map_partitions(
            lambda p, i: p.with_column("y", p["x"] * 2))
        np.testing.assert_array_equal(out["y"], np.arange(20) * 2)
        np.testing.assert_array_equal(out["x"], np.arange(20))

    def test_nested_map_partitions_does_not_deadlock(self):
        # inner call from a pool worker must take the sequential path
        # rather than queue on the same (possibly saturated) executor
        df = DataFrame({"x": np.arange(16)}, npartitions=4)

        def outer(p, i):
            inner = DataFrame({"x": np.asarray(p["x"])}, npartitions=2)
            return inner.map_partitions(
                lambda q, j: q.with_column("y", q["x"] + 1))

        out = df.map_partitions(outer)
        np.testing.assert_array_equal(out["y"], np.arange(16) + 1)

    def test_exception_still_propagates(self):
        df = DataFrame({"x": np.arange(8)}, npartitions=4)

        def boom(p, i):
            if i == 2:
                raise RuntimeError("partition 2 failed")
            return p

        with pytest.raises(RuntimeError, match="partition 2"):
            df.map_partitions(boom)
