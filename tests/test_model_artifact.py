"""Portable model artifacts (mmlspark_tpu.mlflow).

Parity: the reference's generated PyTest saves every fitted model through
mlflow and reloads it as a generic pyfunc (``core/src/test/scala/com/
microsoft/azure/synapse/ml/core/test/fuzzing/Fuzzing.scala:135-140``).
These tests pin the artifact *format* (MLmodel descriptor parseable by real
YAML, pyfunc loader hook, mlruns layout) and the *capability* (reload in a
separate fresh process with identical predictions)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.core.pipeline import Pipeline
from mmlspark_tpu.featurize import ValueIndexer
from mmlspark_tpu.mlflow import (PyFuncModel, infer_signature, load_model,
                                 log_model, save_model, _load_pyfunc)
from mmlspark_tpu.train import TrainClassifier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fitted_model_and_df():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    df = DataFrame({"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2],
                    "f3": X[:, 3], "label": y})
    est = TrainClassifier(label_col="label")
    return Pipeline([est]).fit(df), df


class TestSaveLoad:
    def test_roundtrip_identical_predictions(self, tmp_path):
        model, df = _fitted_model_and_df()
        ref = model.transform(df)
        p = str(tmp_path / "artifact")
        save_model(model, p, input_example=df)
        loaded = load_model(p)
        assert isinstance(loaded, PyFuncModel)
        out = loaded.predict(df)
        np.testing.assert_array_equal(np.asarray(ref["prediction"]),
                                      np.asarray(out["prediction"]))

    def test_predict_accepts_plain_dict(self, tmp_path):
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p)
        out = load_model(p).predict(
            {c: np.asarray(df[c]) for c in df.columns})
        assert "prediction" in out.columns

    def test_predict_pandas_in_pandas_out(self, tmp_path):
        """mlflow.pyfunc contract: pandas in → pandas out."""
        import pandas as pd
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p)
        pdf = pd.DataFrame({c: np.asarray(df[c]) for c in df.columns})
        out = load_model(p).predict(pdf)
        assert isinstance(out, pd.DataFrame)
        np.testing.assert_array_equal(
            out["prediction"].to_numpy(),
            np.asarray(model.transform(df)["prediction"]))

    def test_mlmodel_descriptor_is_valid_yaml_with_pyfunc_flavor(
            self, tmp_path):
        yaml = pytest.importorskip("yaml")
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p, input_example=df)
        with open(os.path.join(p, "MLmodel")) as fh:
            meta = yaml.safe_load(fh)
        pf = meta["flavors"]["python_function"]
        assert pf["loader_module"] == "mmlspark_tpu.mlflow"
        assert os.path.isdir(os.path.join(p, pf["data"]))
        assert "model_uuid" in meta
        # signature columns parse back as json (mlflow stores them encoded)
        sig = json.loads(meta["signature"]["inputs"])
        assert {c["name"] for c in sig} >= {"f0", "label"}
        assert os.path.exists(os.path.join(p, "requirements.txt"))

    def test_pyfunc_loader_hook(self, tmp_path):
        """_load_pyfunc(data_path) is what genuine mlflow.pyfunc calls."""
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p)
        wrapped = _load_pyfunc(os.path.join(p, "stage"))
        assert "prediction" in wrapped.predict(df).columns

    def test_fresh_process_reload(self, tmp_path):
        """The artifact is self-describing: a separate python process with
        no access to this test's state reloads and predicts identically."""
        model, df = _fitted_model_and_df()
        ref = np.asarray(model.transform(df)["prediction"])
        p = str(tmp_path / "artifact")
        save_model(model, p)
        np.save(str(tmp_path / "inputs.npy"),
                np.stack([np.asarray(df[c]) for c in
                          ("f0", "f1", "f2", "f3", "label")]))
        code = (
            "import sys, numpy as np\n"
            "from mmlspark_tpu.mlflow import load_model\n"
            f"cols = np.load({str(tmp_path / 'inputs.npy')!r})\n"
            "data = dict(zip(('f0','f1','f2','f3','label'), cols))\n"
            f"out = load_model({p!r}).predict(data)\n"
            "np.save(sys.argv[1], np.asarray(out['prediction']))\n")
        outp = str(tmp_path / "pred.npy")
        # JAX_PLATFORMS=cpu is inherited from conftest's os.environ
        env = {**os.environ, "PYTHONPATH": REPO}
        r = subprocess.run([sys.executable, "-c", code, outp],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        np.testing.assert_array_equal(ref, np.load(outp))


class TestLogModel:
    def test_mlruns_layout(self, tmp_path):
        model, df = _fitted_model_and_df()
        dest = log_model(model, "model", tracking_dir=str(tmp_path / "mlruns"))
        # <tracking>/<run_id>/artifacts/model
        rel = os.path.relpath(dest, str(tmp_path / "mlruns"))
        parts = rel.split(os.sep)
        assert parts[1] == "artifacts" and parts[2] == "model"
        assert "prediction" in load_model(dest).predict(df).columns


class TestSignature:
    def test_infer_signature_shapes(self):
        df = DataFrame({"x": np.arange(4, dtype=np.float32),
                        "s": np.array(["a", "b", "c", "d"], dtype=object)})
        sig = infer_signature(df)
        byname = {c["name"]: c["type"] for c in sig["inputs"]}
        assert byname["x"] == "float32"

    def test_transformer_artifact(self, tmp_path):
        """Non-fitted transformers are artifacts too (any stage works)."""
        df = DataFrame({"cat": np.array(["a", "b", "a", "c"], dtype=object)})
        model = ValueIndexer(input_col="cat", output_col="idx").fit(df)
        p = str(tmp_path / "vi")
        save_model(model, p, input_example=df)
        out = load_model(p).predict(df)
        np.testing.assert_array_equal(np.asarray(out["idx"]),
                                      np.asarray(model.transform(df)["idx"]))


class TestOverwrite:
    def test_refuses_non_empty_path(self, tmp_path):
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p)
        with pytest.raises(FileExistsError, match="overwrite"):
            save_model(model, p)
        save_model(model, p, overwrite=True)    # replaces cleanly
        assert "prediction" in load_model(p).predict(df).columns

    def test_overwrite_clears_stale_files(self, tmp_path):
        model, df = _fitted_model_and_df()
        p = str(tmp_path / "artifact")
        save_model(model, p, input_example=df)
        assert os.path.exists(os.path.join(p, "input_example.json"))
        save_model(model, p, overwrite=True)    # no example this time
        assert not os.path.exists(os.path.join(p, "input_example.json"))

