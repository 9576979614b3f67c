"""VW-style online linear learners on hashed sparse features.

Parity surface: ``VowpalWabbitClassifier`` / ``VowpalWabbitRegressor`` and the
training orchestration of ``VowpalWabbitBase`` (``vw/.../VowpalWabbitBase.scala``):
multiple passes over the data, adaptive (adagrad) importance-weighted updates,
squared / logistic / hinge / quantile losses, per-pass distributed weight
AllReduce (``--span_server``, ``VowpalWabbitBase.scala:432-460``), and a
per-fit performance-statistics table (``TrainingStats``,
``VowpalWabbitBase.scala:25-47,473-487``).

TPU-native redesign (not a port): VW's per-example C++ loop becomes one jitted
``lax.scan`` over fixed-size minibatches. Each step gathers the touched
weights (``w[idx]``), computes the loss gradient, and scatter-adds adagrad
statistics and updates — XLA lowers gather/scatter to native TPU ops, and the
whole multi-pass optimization is a single compiled program. Data parallelism
shards rows over a mesh axis and averages weights with ``lax.pmean`` after
every pass, exactly the synchronization VW's spanning-tree AllReduce performs.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import numpy as np

from ..core.dataframe import DataFrame
from ..core.params import (ComplexParam, HasFeaturesCol, HasLabelCol,
                           HasPredictionCol, HasProbabilityCol, HasWeightCol,
                           Param)
from ..core.pipeline import Estimator, Model
from .featurizer import NUM_BITS_KEY

__all__ = ["VowpalWabbitClassifier", "VowpalWabbitClassifierModel",
           "VowpalWabbitRegressor", "VowpalWabbitRegressorModel"]


# ---------------------------------------------------------------------------
# Sparse batch marshalling: object rows → padded static-shape device arrays
# ---------------------------------------------------------------------------

def pad_sparse(col, max_nnz: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """(indices, values) object rows → (idx [n, K] int32, val [n, K] f32).

    Padding slots get index 0 with value 0 — a zero-value feature is a no-op
    for both prediction (contributes 0) and the gradient (scales by value).
    """
    if max_nnz is None:
        max_nnz = max((len(r[0]) for r in col), default=0)
    K = max(1, max_nnz)
    from ..native import pad_sparse as native_pad
    return native_pad(list(col), K)


def _make_pass_fn(loss: str, quantile_tau: float, n_passes: int,
                  batch: int, axis: Optional[str]):
    """Build the jitted multi-pass trainer. ``axis`` names the mesh axis to
    pmean weights over after each pass (None = single device)."""
    import jax
    import jax.numpy as jnp

    def dloss(pred, y, sw):
        if loss == "squared":
            return (pred - y) * sw
        if loss == "logistic":        # y in {-1, +1}
            return -y * jax.nn.sigmoid(-y * pred) * sw
        if loss == "hinge":           # y in {-1, +1}
            return jnp.where(y * pred < 1.0, -y, 0.0) * sw
        if loss == "quantile":
            return jnp.where(pred > y, 1.0 - quantile_tau, -quantile_tau) * sw
        raise ValueError(f"unknown loss {loss!r}")

    def run(w, G, idx, val, y, sw, lr, l1, l2, power_t):
        """idx/val: [n_batches, B, K]; y/sw: [n_batches, B]."""
        if axis is not None:
            # entering shard_map replicated; updates indexed by sharded rows
            # make the carry device-varying, so mark it varying up front
            w = jax.lax.pcast(w, (axis,), to="varying")
            G = jax.lax.pcast(G, (axis,), to="varying")

        def minibatch_step(carry, xs):
            w, G, t = carry
            bidx, bval, by, bsw = xs
            pred = jnp.sum(w[bidx] * bval, axis=-1)          # [B] gather+dot
            d = dloss(pred, by, bsw)                          # [B]
            g = d[:, None] * bval                             # [B, K] per-feature grad
            # adagrad accumulate, then scale: scatter-adds coalesce duplicate
            # indices inside the batch, which is the correct sum-of-squares /
            # summed-gradient semantics for minibatch adagrad
            G = G.at[bidx].add(g * g)
            denom = jnp.sqrt(G[bidx]) + 1e-6
            # decayed base rate: lr * (t+1)^-power_t, VW's power_t schedule
            step = lr * (t + 1.0) ** (-power_t)
            upd = step * g / denom
            w = w.at[bidx].add(-upd)
            # proximal-ish shrinkage on touched coords only (sparse l1/l2);
            # padding slots (index 0, value 0) must not count as touched or
            # bucket 0 gets over-regularized every step
            if True:
                wt = w[bidx]
                shrunk = jnp.sign(wt) * jnp.maximum(jnp.abs(wt) - step * l1, 0.0)
                shrunk = shrunk * (1.0 - step * l2)
                w = w.at[bidx].set(jnp.where(bval != 0.0, shrunk, wt))
            return (w, G, t + 1.0), None

        def one_pass(carry, _):
            w, G, t = carry
            (w, G, t), _ = jax.lax.scan(minibatch_step, (w, G, t),
                                        (idx, val, y, sw))
            if axis is not None:
                w = jax.lax.pmean(w, axis)   # per-pass AllReduce (VW parity)
                w = jax.lax.pcast(w, (axis,), to="varying")
            return (w, G, t), None

        (w, G, _), _ = jax.lax.scan(one_pass, (w, G, 0.0), None,
                                    length=n_passes)
        if axis is not None:
            # replicate the outputs: w is already synced (identity pmean);
            # G merges into an averaged accumulator for warm starts
            w = jax.lax.pmean(w, axis)
            G = jax.lax.pmean(G, axis)
        return w, G

    return run


_PASS_CACHE: dict = {}


def _pass_fn(loss, tau, n_passes, batch, axis):
    import jax
    key = (loss, float(tau), int(n_passes), int(batch), axis)
    if key not in _PASS_CACHE:
        _PASS_CACHE[key] = jax.jit(_make_pass_fn(loss, tau, n_passes, batch, axis))
    return _PASS_CACHE[key]


# ---------------------------------------------------------------------------
# Base estimator
# ---------------------------------------------------------------------------

class _VWParams(HasFeaturesCol, HasLabelCol, HasWeightCol):
    num_passes = Param(int, default=1, doc="passes over the data")
    learning_rate = Param(float, default=0.5, doc="base learning rate (VW default 0.5)")
    power_t = Param(float, default=0.5, doc="learning-rate decay exponent")
    l1 = Param(float, default=0.0, doc="L1 regularization (per-update shrink)")
    l2 = Param(float, default=0.0, doc="L2 regularization (per-update decay)")
    num_bits = Param(int, default=18, doc="log2 weight-space size; overridden "
                                          "by featurizer column metadata")
    mini_batch = Param(int, default=64, doc="rows per device update step "
                                            "(TPU-first stand-in for VW's "
                                            "per-example loop)")
    use_all_reduce = Param(bool, default=True,
                           doc="shard rows over the default mesh and pmean "
                               "weights each pass (VW --span_server parity)")
    initial_model = ComplexParam(default=None, doc="warm-start weight vector")
    initial_adaptive_state = ComplexParam(
        default=None, doc="warm-start adagrad accumulator (VW --save_resume "
                          "parity; take it from a fitted model's "
                          "adaptive_state param)")
    seed = Param(int, default=0, doc="unused (training is deterministic); "
                                     "kept for API parity")


class _VWBase(Estimator, _VWParams):
    _loss: str = "squared"
    quantile_tau = Param(float, default=0.5, doc="tau for quantile loss")

    def _labels(self, df: DataFrame) -> np.ndarray:
        raise NotImplementedError

    def _num_bits(self, df: DataFrame) -> int:
        meta = df.column_metadata(self.get("features_col"))
        return int(meta.get(NUM_BITS_KEY, self.get("num_bits")))

    def _fit(self, df: DataFrame) -> "Model":
        t0 = time.perf_counter()
        import jax
        import jax.numpy as jnp

        fcol = df[self.get("features_col")]
        bits = self._num_bits(df)
        dim = 1 << bits
        idx, val = pad_sparse(fcol)
        n, K = idx.shape
        y = self._labels(df).astype(np.float32)
        wcol = self.get_or_none("weight_col")
        sw = (df[wcol].astype(np.float32) if wcol
              else np.ones(n, dtype=np.float32))

        B = min(self.get("mini_batch"), max(1, n))
        # shard rows across the default mesh when requested & available
        from ..parallel.mesh import get_default_mesh
        mesh = get_default_mesh() if self.get("use_all_reduce") else None
        n_shards = int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1

        # pad row count to n_shards * B multiple with zero-weight rows
        per = -(-n // (n_shards * B)) * B            # rows per shard, multiple of B
        total = per * n_shards
        pad = total - n
        if pad:
            idx = np.vstack([idx, np.zeros((pad, K), np.int32)])
            val = np.vstack([val, np.zeros((pad, K), np.float32)])
            y = np.concatenate([y, np.zeros(pad, np.float32)])
            sw = np.concatenate([sw, np.zeros(pad, np.float32)])

        w0 = self.get_or_none("initial_model")
        w0 = (np.zeros(dim, np.float32) if w0 is None
              else np.asarray(w0, np.float32).copy())
        if len(w0) != dim:
            raise ValueError(f"initial_model has {len(w0)} weights, expected {dim}")
        G0 = self.get_or_none("initial_adaptive_state")
        G0 = (np.full(dim, 1e-12, np.float32) if G0 is None
              else np.asarray(G0, np.float32).copy())

        n_batches = per // B
        tau = self.get("quantile_tau")
        passes = self.get("num_passes")
        lr = jnp.float32(self.get("learning_rate"))
        l1 = jnp.float32(self.get("l1"))
        l2 = jnp.float32(self.get("l2"))
        pt = jnp.float32(self.get("power_t"))

        if mesh is not None and n_shards > 1:
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import get_shard_map
            shard_map, _ = get_shard_map()
            axis = mesh.axis_names[0]
            run = _make_pass_fn(self._loss, tau, passes, B, axis)

            def sharded(w, G, idx, val, y, sw):
                w, G = run(w.reshape(-1), G.reshape(-1),
                           idx.reshape(n_batches, B, K),
                           val.reshape(n_batches, B, K),
                           y.reshape(n_batches, B), sw.reshape(n_batches, B),
                           lr, l1, l2, pt)
                return w, G

            spec_rows = P(axis)
            fn = jax.jit(shard_map(
                sharded, mesh=mesh,
                in_specs=(P(), P(), spec_rows, spec_rows, spec_rows, spec_rows),
                out_specs=(P(), P())))
            w, G = fn(jnp.asarray(w0), jnp.asarray(G0), jnp.asarray(idx),
                      jnp.asarray(val), jnp.asarray(y), jnp.asarray(sw))
        else:
            run = _pass_fn(self._loss, tau, passes, B, None)
            w, G = run(jnp.asarray(w0), jnp.asarray(G0),
                       jnp.asarray(idx.reshape(n_batches, B, K)),
                       jnp.asarray(val.reshape(n_batches, B, K)),
                       jnp.asarray(y.reshape(n_batches, B)),
                       jnp.asarray(sw.reshape(n_batches, B)),
                       lr, l1, l2, pt)
        w = np.asarray(jax.block_until_ready(w))

        model = self._make_model()
        model.set(features_col=self.get("features_col"),
                  weights=w, num_bits=bits,
                  adaptive_state=np.asarray(G))
        elapsed = time.perf_counter() - t0
        # TrainingStats parity (VowpalWabbitBase.scala:25-47): one row per
        # data shard with timing/size diagnostics
        model.performance_statistics = DataFrame({
            "partitionId": np.arange(n_shards),
            "rows": np.full(n_shards, n // max(n_shards, 1)),
            "passes": np.full(n_shards, passes),
            "totalSeconds": np.full(n_shards, round(elapsed, 4)),
            "weightsNonZero": np.full(n_shards, int((w != 0).sum())),
        })
        return model

    def _make_model(self) -> "Model":
        raise NotImplementedError


class _VWModelBase(Model, HasFeaturesCol, HasPredictionCol):
    weights = ComplexParam(default=None, doc="hashed weight vector (2^num_bits)")
    adaptive_state = ComplexParam(default=None,
                                  doc="adagrad accumulator for warm starts")
    num_bits = Param(int, default=18, doc="log2 weight-space size")

    def _raw_scores(self, df: DataFrame) -> np.ndarray:
        idx, val = pad_sparse(df[self.get("features_col")])
        w = np.asarray(self.get("weights"))
        return (w[idx] * val).sum(axis=1)


class VowpalWabbitRegressor(_VWBase, HasPredictionCol):
    """Online linear regression (squared or quantile loss)."""

    loss_function = Param(str, default="squared",
                          choices=["squared", "quantile"],
                          doc="training loss")

    @property
    def _loss(self):
        return self.get("loss_function")

    def _labels(self, df: DataFrame) -> np.ndarray:
        return np.asarray(df[self.get("label_col")], dtype=np.float32)

    def _make_model(self):
        m = VowpalWabbitRegressorModel()
        m.set(prediction_col=self.get("prediction_col"))
        return m


class VowpalWabbitRegressorModel(_VWModelBase):
    def _transform(self, df: DataFrame) -> DataFrame:
        return df.with_column(self.get("prediction_col"), self._raw_scores(df))


class VowpalWabbitClassifier(_VWBase, HasPredictionCol, HasProbabilityCol):
    """Binary classifier (labels {0,1}), logistic or hinge loss."""

    loss_function = Param(str, default="logistic",
                          choices=["logistic", "hinge"],
                          doc="training loss")

    @property
    def _loss(self):
        return self.get("loss_function")

    def _labels(self, df: DataFrame) -> np.ndarray:
        y = np.asarray(df[self.get("label_col")], dtype=np.float32)
        uniq = np.unique(y)
        if not np.all(np.isin(uniq, [0.0, 1.0, -1.0])):
            raise ValueError(f"binary labels must be 0/1 (or ±1), got {uniq}")
        return np.where(y > 0, 1.0, -1.0)   # VW's ±1 convention

    def _make_model(self):
        m = VowpalWabbitClassifierModel()
        m.set(prediction_col=self.get("prediction_col"),
              probability_col=self.get("probability_col"))
        return m


class VowpalWabbitClassifierModel(_VWModelBase, HasProbabilityCol):
    raw_prediction_col = Param(str, default="rawPrediction",
                               doc="column for the raw margin")

    def _transform(self, df: DataFrame) -> DataFrame:
        raw = self._raw_scores(df)
        prob = 1.0 / (1.0 + np.exp(-raw))
        return (df.with_column(self.get("raw_prediction_col"), raw)
                  .with_column(self.get("probability_col"), prob)
                  .with_column(self.get("prediction_col"),
                               (raw > 0).astype(np.float64)))
