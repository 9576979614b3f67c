"""Native fastpath extension: correctness vs pure-Python fallbacks, and
the fallback path itself (MMLSPARK_TPU_NO_NATIVE=1)."""

import numpy as np
import pytest

from mmlspark_tpu import native
from mmlspark_tpu.vw.murmur import _murmur3_32_py

VECTORS = [b"", b"a", b"hello", b"hello, world",
           b"The quick brown fox jumps over the lazy dog", b"\x00\xff" * 7]


def test_native_builds():
    assert native.available(), "g++ toolchain present; extension must build"


class TestBinColumns:
    """Native quantile binning == searchsorted(bounds, x, 'left') + 1 with
    NaN -> 0 (the GBDT dataset-construction hot loop, LightGBM's
    LGBM_DatasetCreateFromMat role)."""

    @staticmethod
    def _ref(X, bounds_list):
        n, f = X.shape
        out = np.zeros((n, f), np.int64)
        for j in range(f):
            col = X[:, j]
            b = np.searchsorted(bounds_list[j], col, side="left") + 1
            out[:, j] = np.where(np.isnan(col), 0, b)
        return out

    @staticmethod
    def _table(bounds_list):
        lengths = np.array([len(b) for b in bounds_list], np.int64)
        table = np.full((len(bounds_list), lengths.max()), np.inf)
        for j, b in enumerate(bounds_list):
            table[j, :len(b)] = b
        return table, lengths

    @pytest.mark.parametrize("gen", ["gauss", "cauchy", "const", "inf"])
    def test_matches_searchsorted(self, gen):
        # fixed seeds: hash(str) varies per process (PYTHONHASHSEED), which
        # would make a boundary failure unreproducible
        rng = np.random.default_rng(
            {"gauss": 11, "cauchy": 22, "const": 33, "inf": 44}[gen])
        n, f = 40_000, 5
        X = {"gauss": lambda: rng.normal(0, 1, (n, f)),
             "cauchy": lambda: rng.standard_cauchy((n, f)),
             "const": lambda: np.full((n, f), 2.5),
             "inf": lambda: np.where(rng.random((n, f)) < 0.05,
                                     np.inf * rng.choice([-1, 1], (n, f)),
                                     rng.normal(0, 1, (n, f)))}[gen]() \
            .astype(np.float32)
        X[rng.random((n, f)) < 0.03] = np.nan
        bounds = []
        for j in range(f):
            col = X[:, j]
            col = col[np.isfinite(col)]
            qs = (np.unique(np.quantile(col, np.linspace(0, 1, 100)))
                  if col.size else np.array([]))
            bounds.append(np.append(qs, np.inf))
        table, lengths = self._table(bounds)
        got = native.bin_columns(X, table, lengths, False)
        assert got.dtype == np.uint8
        assert np.array_equal(got.astype(np.int64), self._ref(X, bounds))

    def test_uint16_and_float64(self):
        rng = np.random.default_rng(7)
        X = rng.normal(0, 1, (5_000, 3)).astype(np.float64)
        bounds = [np.append(np.sort(rng.normal(0, 1, 500)), np.inf)
                  for _ in range(3)]
        table, lengths = self._table(bounds)
        got = native.bin_columns(X, table, lengths, True)
        assert got.dtype == np.uint16
        assert np.array_equal(got.astype(np.int64), self._ref(X, bounds))

    def test_fallback_matches_native(self, monkeypatch):
        rng = np.random.default_rng(9)
        X = rng.normal(0, 1, (2_000, 4)).astype(np.float32)
        bounds = [np.append(np.sort(rng.normal(0, 1, 30)), np.inf)
                  for _ in range(4)]
        table, lengths = self._table(bounds)
        a = native.bin_columns(X, table, lengths, False)
        monkeypatch.setattr(native, "_impl", False)
        b = native.bin_columns(X, table, lengths, False)
        monkeypatch.setattr(native, "_impl", None)
        assert np.array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 1, 0xDEADBEEF])
def test_murmur3_matches_reference(seed):
    for v in VECTORS:
        assert native.murmur3(v, seed) == _murmur3_32_py(v, seed)


def test_murmur3_batch():
    got = native.murmur3_batch(VECTORS, 7, 0xFFFFF)
    want = [_murmur3_32_py(v, 7) & 0xFFFFF for v in VECTORS]
    assert got.dtype == np.uint32
    assert list(got) == want


def test_pad_sparse_matches_fallback():
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(20):
        k = int(rng.integers(0, 6))
        rows.append((rng.integers(0, 1000, k).astype(np.uint32),
                     rng.random(k).astype(np.float32)))
    ni, nv = native.pad_sparse(rows, 6)
    impl = native._impl
    try:
        native._impl = False
        fi, fv = native.pad_sparse(rows, 6)
    finally:
        native._impl = impl
    np.testing.assert_array_equal(ni, fi)
    np.testing.assert_array_equal(nv, fv)


def test_stack_rows_pads_and_truncates():
    out = native.stack_rows([np.arange(3.0), np.arange(6.0)], 4)
    assert out.shape == (2, 4)
    assert out[0, 3] == 0.0 and out[1, 3] == 3.0


def test_featurizer_uses_batch_path_consistently():
    """String columns (batch-hashed) must produce identical features to the
    per-value path (hash compatibility native vs python)."""
    from mmlspark_tpu.core import DataFrame
    from mmlspark_tpu.vw import VowpalWabbitFeaturizer
    df = DataFrame({"t": np.array(["a b", "c", ""], dtype=object)})
    f = VowpalWabbitFeaturizer(input_cols=["t"], string_split_cols=["t"],
                               num_bits=14)
    out1 = f.transform(df)["features"]
    impl = native._impl
    try:
        native._impl = False
        import mmlspark_tpu.vw.murmur as mm
        mm._native_fn = False
        out2 = f.transform(df)["features"]
    finally:
        native._impl = impl
        import mmlspark_tpu.vw.murmur as mm
        mm._native_fn = None
    for (i1, v1), (i2, v2) in zip(out1, out2):
        np.testing.assert_array_equal(np.sort(i1), np.sort(i2))


def test_pad_sparse_malformed_row_clamps_both_paths():
    rows = [(np.array([1, 2, 3], np.uint32), np.array([0.5, 0.25], np.float32))]
    ni, nv = native.pad_sparse(rows, 3)
    impl = native._impl
    try:
        native._impl = False
        fi, fv = native.pad_sparse(rows, 3)
    finally:
        native._impl = impl
    np.testing.assert_array_equal(ni, fi)
    np.testing.assert_array_equal(nv, fv)
    assert nv[0, 2] == 0.0          # never reads past the values buffer


class TestParseLibsvm:
    DATA = (b"1 1:0.5 3:2.0 # trailing comment\n"
            b"\n"
            b"-1 2:1.5\n"
            b"0 qid:7 1:1.0 4:-2.5\n"
            b"# full-line comment\n"
            b"2.5\n")                       # label-only row (all-zero features)

    def _check(self, parse):
        labels, qids, indptr, indices, values = parse(self.DATA)
        np.testing.assert_allclose(labels, [1, -1, 0, 2.5])
        np.testing.assert_array_equal(qids, [-1, -1, 7, -1])
        np.testing.assert_array_equal(indptr, [0, 2, 3, 5, 5])
        np.testing.assert_array_equal(indices, [1, 3, 2, 1, 4])
        np.testing.assert_allclose(values, [0.5, 2.0, 1.5, 1.0, -2.5])

    def test_python_fallback(self, monkeypatch):
        import mmlspark_tpu.native as nat
        monkeypatch.setattr(nat, "_impl", False)
        self._check(nat.parse_libsvm)

    def test_native_if_available(self):
        import mmlspark_tpu.native as nat
        if not nat.available():
            pytest.skip("no native toolchain")
        self._check(nat.parse_libsvm)

    def test_native_matches_python(self):
        import mmlspark_tpu.native as nat
        if not nat.available():
            pytest.skip("no native toolchain")
        rng = np.random.default_rng(0)
        lines = []
        for i in range(200):
            feats = sorted(rng.choice(50, size=rng.integers(0, 8),
                                      replace=False))
            toks = [f"{rng.normal():.6f}"]
            if i % 3 == 0:
                toks.append(f"qid:{i // 10}")
            toks += [f"{f + 1}:{rng.normal():.6f}" for f in feats]
            lines.append(" ".join(toks))
        data = ("\n".join(lines)).encode()
        native = nat._load().parse_libsvm(data)
        prev, nat._impl = nat._impl, False
        try:
            pure = nat.parse_libsvm(data)
        finally:
            nat._impl = prev
        for a, b in zip(native, pure):
            np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_bad_token_raises(self):
        import mmlspark_tpu.native as nat
        with pytest.raises(ValueError):
            nat.parse_libsvm(b"1 nocolon\n")


class TestReadLibsvm:
    def test_roundtrip_to_gbdt(self, tmp_path):
        from mmlspark_tpu.io import read_libsvm
        from mmlspark_tpu.models.gbdt import LightGBMClassifier

        rng = np.random.default_rng(1)
        X = rng.normal(0, 1, (200, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(int)
        p = tmp_path / "d.svm"
        with open(p, "w") as f:
            for i in range(len(X)):
                feats = " ".join(f"{j + 1}:{X[i, j]:.6f}" for j in range(6))
                f.write(f"{y[i]} {feats}\n")
        df = read_libsvm(str(p))
        assert df["features"][0].shape == (6,)
        np.testing.assert_allclose(
            np.stack(list(df["features"])), X, rtol=1e-5, atol=1e-6)
        m = LightGBMClassifier(num_iterations=10,
                               min_data_in_leaf=5).fit(df)
        acc = (np.asarray(m.transform(df)["prediction"])
               == np.asarray(df["label"])).mean()
        assert acc > 0.9

    def test_qid_becomes_group(self, tmp_path):
        from mmlspark_tpu.io import read_libsvm
        p = tmp_path / "r.svm"
        p.write_text("1 qid:1 1:0.5\n0 qid:1 1:0.1\n1 qid:2 1:0.9\n")
        df = read_libsvm(str(p))
        np.testing.assert_array_equal(df["group"], [1, 1, 2])

    def test_zero_based_autodetect(self, tmp_path):
        from mmlspark_tpu.io import read_libsvm
        p = tmp_path / "z.svm"
        p.write_text("1 0:2.0 2:3.0\n0 1:1.0\n")
        df = read_libsvm(str(p))
        np.testing.assert_allclose(df["features"][0], [2.0, 0.0, 3.0])


class TestLibsvmReviewRegressions:
    def test_out_of_range_index_errors_not_wraps(self):
        import mmlspark_tpu.native as nat
        if not nat.available():
            pytest.skip("no native toolchain")
        with pytest.raises((ValueError, OverflowError)):
            nat._load().parse_libsvm(b"1 4294967297:2.0\n")

    def test_partial_qid_coverage_rejected(self, tmp_path):
        from mmlspark_tpu.io import read_libsvm
        p = tmp_path / "p.svm"
        p.write_text("1 1:0.5\n0 qid:1 1:0.1\n")
        with pytest.raises(ValueError, match="lack qid"):
            read_libsvm(str(p))


def test_libsvm_truncated_qid_errors_native():
    import mmlspark_tpu.native as nat
    if not nat.available():
        pytest.skip("no native toolchain")
    with pytest.raises(ValueError):
        nat._load().parse_libsvm(b"1 qid:\n5 1:2.0\n")


def test_libsvm_negative_index_rejected_both_parsers():
    import mmlspark_tpu.native as nat
    with pytest.raises(ValueError):
        nat.parse_libsvm(b"1 -1:2.0\n")
    prev, nat._impl = nat._impl, False
    try:
        with pytest.raises(ValueError):
            nat.parse_libsvm(b"1 -1:2.0\n")
    finally:
        nat._impl = prev


class TestBuildError:
    """A fast path that could not be built says why (PR 23): the pure-Python
    path still serves the API, but never in silence."""

    @pytest.fixture
    def fresh_loader(self, monkeypatch, tmp_path):
        """The loader as a checkout finds it: no built ``.so`` on disk."""
        monkeypatch.setattr(native, "_impl", None)
        monkeypatch.setattr(native, "_build_error", None)
        monkeypatch.setattr(native, "_SO", str(tmp_path / "_fastpath_t.so"))
        monkeypatch.delenv("MMLSPARK_TPU_NO_NATIVE", raising=False)
        return tmp_path

    def test_none_when_loaded(self):
        assert native.available()
        assert native.build_error() is None

    def test_compiler_stderr_is_reported(self, fresh_loader, monkeypatch):
        bad = fresh_loader / "broken.cpp"
        bad.write_text("this is not C++\n")
        monkeypatch.setattr(native, "_SRC", str(bad))
        assert native.available() is False
        err = native.build_error()
        assert err.startswith("g++ exited") and "error" in err

    def test_missing_compiler_is_reported(self, fresh_loader, monkeypatch):
        def no_gxx(*a, **kw):
            raise FileNotFoundError("g++")
        monkeypatch.setattr(native.subprocess, "run", no_gxx)
        assert native.available() is False
        assert "FileNotFoundError" in native.build_error()

    def test_opt_out_is_reported(self, fresh_loader, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_NO_NATIVE", "1")
        assert native.available() is False
        assert "MMLSPARK_TPU_NO_NATIVE" in native.build_error()

    def test_builds_from_source_with_the_so_absent(self, fresh_loader):
        # _SO points at a path that does not exist yet: the loader must
        # compile fastpath.cpp there, as in a fresh checkout
        assert not native.os.path.exists(native._SO)
        assert native._compile() is True
        assert native.os.path.exists(native._SO)
