"""GBDT boosting loop.

Parity surface: the reference's training orchestration
(``lightgbm/.../LightGBMBase.scala:43-66`` batch loop, ``TrainUtils.scala:92-160``
iteration loop with eval metrics and early stopping ``:126-152``; rank-0
model return ``:356-364``) and LightGBM's parameter surface rendered by
``params/TrainParams.scala:10-100``.

TPU-first structure: grad/hess, tree build, and score update are jitted and
stay on device between iterations; only eval metrics come back to host. The
``tree_learner='data_parallel'`` path wraps the tree builder in ``shard_map``
over the mesh's ``data`` axis — histograms psum over ICI, every shard makes
identical split decisions (the same invariant LightGBM's socket allreduce
maintains), rows never move. Multiclass trains K trees per iteration via
``vmap`` over the class axis — the K histograms batch into one kernel.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...observability import histogram as _metric_histogram
from .binning import BinMapper
from .booster import Booster
from .objectives import get_metric, get_objective
from .trees import build_tree

_M_GBDT_PHASE = _metric_histogram(
    "mmlspark_gbdt_phase_seconds",
    "Per-iteration GBDT training phase wall-clock (populated only when "
    "MMLSPARK_TPU_GBDT_PROF=1, like the _PhaseProf stderr report)",
    ("phase",))

__all__ = ["train", "TrainConfig", "resolve_params"]

_DEFAULTS = dict(
    objective="regression",
    boosting="gbdt",                # gbdt | goss | dart | rf
    top_rate=0.2,                   # goss: keep fraction by |grad|
    other_rate=0.1,                 # goss: sample fraction of the rest
    drop_rate=0.1,                  # dart: per-tree drop probability
    max_drop=50,                    # dart: cap on dropped trees per iter
    skip_drop=0.5,                  # dart: prob of skipping the drop entirely
    num_iterations=100,
    learning_rate=0.1,
    num_leaves=31,
    max_depth=-1,
    lambda_l1=0.0,
    lambda_l2=0.0,
    min_data_in_leaf=20,
    min_sum_hessian_in_leaf=1e-3,
    min_gain_to_split=0.0,
    feature_fraction=1.0,
    bagging_fraction=1.0,
    bagging_freq=0,
    max_bin=255,
    early_stopping_round=0,
    num_class=1,
    seed=0,
    metric="auto",
    tree_learner="serial",
    top_k=20,                       # voting_parallel: local nominations/node
    alpha=0.9,                      # huber/quantile parameter
    tweedie_variance_power=1.5,
    verbosity=-1,
    checkpoint_dir=None,            # step-level checkpoint/resume
    checkpoint_interval=0,          # iterations between checkpoints (0 = off)
    categorical_feature=None,       # feature indices with categorical splits
    enable_bundle=True,             # EFB on sparse input (LightGBM name)
    max_conflict_rate=0.0,          # EFB conflict budget as a row fraction
    max_bundle_bins=4096,           # cap on one bundle's bin span
    monotone_constraints=None,      # per-feature -1/0/+1 (LightGBM name)
    scale_pos_weight=1.0,           # binary: positive-class weight multiplier
    is_unbalance=False,             # binary: auto scale_pos_weight = neg/pos
    extra_trees=False,              # one random threshold per node×feature
    feature_fraction_bynode=1.0,    # feature subsample per NODE (not tree)
    path_smooth=0.0,                # smooth node outputs toward the parent
    boost_from_average=True,        # start from the objective's optimal const
    interaction_constraints=None,   # list of allowed feature groups
    cat_smooth=10.0,                # categorical: mean smoothing pseudo-count
    min_data_per_group=0,           # categorical: pool rarer categories
    linear_tree=False,              # ridge model per leaf over path features
    linear_lambda=0.0,              # L2 on linear-leaf weights (not bias)
    use_quantized_grad=False,       # bf16 histogram stats on the MXU
    #                                 (LightGBM's quantized-gradient analog)
)


def resolve_params(params: Dict) -> Dict:
    aliases = {"n_estimators": "num_iterations", "num_trees": "num_iterations",
               "num_round": "num_iterations", "eta": "learning_rate",
               "reg_alpha": "lambda_l1", "reg_lambda": "lambda_l2",
               "min_child_samples": "min_data_in_leaf",
               "min_child_weight": "min_sum_hessian_in_leaf",
               "subsample": "bagging_fraction", "subsample_freq": "bagging_freq",
               "colsample_bytree": "feature_fraction",
               "min_split_gain": "min_gain_to_split",
               "random_state": "seed",
               "application": "objective", "app": "objective",
               "boosting_type": "boosting", "boost": "boosting",
               "topK": "top_k",
               "parallelism": "tree_learner"}
    out = dict(_DEFAULTS)
    for k, v in params.items():
        out[aliases.get(k, k)] = v
    return out


def _depth_for(p: Dict) -> int:
    if p["max_depth"] and p["max_depth"] > 0:
        return int(p["max_depth"])
    # complete tree with num_leaves leaves at the bottom
    return max(1, int(math.ceil(math.log2(max(2, int(p["num_leaves"]))))))


def _thr_bins_to_raw(feats: np.ndarray, thr_bin: np.ndarray,
                     mapper: BinMapper, n_bins: int) -> np.ndarray:
    """Map split bins → raw thresholds ("x <= thr" ≡ "bin <= thr_bin").

    Fully vectorized over (tree, node) via the mapper's padded bounds table —
    the per-entry Python loop was a HIGGS-scale bottleneck (trees × nodes
    entries per iteration).
    """
    table, lengths = mapper.bounds_table()
    out = np.full(thr_bin.shape, np.inf, dtype=np.float32)
    valid = (feats >= 0) & (thr_bin < n_bins)
    f = np.clip(feats, 0, table.shape[0] - 1).astype(np.int64)
    i = np.clip(thr_bin.astype(np.int64) - 1, 0, np.maximum(lengths[f] - 1, 0))
    vals = table[f, i].astype(np.float32)
    out[valid] = vals[valid]
    return out


def _lambdarank_grad(scores: np.ndarray, y: np.ndarray, groups: np.ndarray,
                     sigma: float = 1.0) -> Tuple[np.ndarray, np.ndarray]:
    """LambdaRank gradients with |ΔNDCG| weighting, per query group.

    Vectorized: groups are padded to the max group size and all pairwise
    terms computed as (chunk, M, M) tensors, chunked so peak memory stays
    bounded — the per-group Python loop was a HIGGS-scale bottleneck.
    """
    g = np.zeros_like(scores)
    h = np.zeros_like(scores)
    groups = np.asarray(groups, dtype=np.int64)
    if len(groups) == 0:
        return g, h
    offs = np.concatenate([[0], np.cumsum(groups)])
    M = int(groups.max())
    if M <= 1:
        return g, h
    nG = len(groups)
    # padded (G, M) row-index matrix + validity mask
    idx = offs[:-1, None] + np.arange(M)[None, :]
    mask = np.arange(M)[None, :] < groups[:, None]
    idx = np.minimum(idx, len(scores) - 1)

    # chunk so the (C, M, M) pair tensors stay ~tens of MB
    chunk = max(1, int(4e6 / (M * M)))
    for lo in range(0, nG, chunk):
        sl = slice(lo, min(lo + chunk, nG))
        m = mask[sl]                                    # (C, M)
        ix = idx[sl]
        cnt = groups[sl]
        s = np.where(m, scores[ix], 0.0)
        yy = np.where(m, y[ix], 0.0)
        # ranks: padded entries sort last via -inf key
        key = np.where(m, s, -np.inf)
        order = np.argsort(-key, axis=1, kind="stable")
        rank = np.empty_like(order)
        np.put_along_axis(rank, order, np.arange(M)[None, :], axis=1)
        gain = np.where(m, 2.0 ** yy - 1, 0.0)
        disc = np.where(m, 1.0 / np.log2(rank + 2.0), 0.0)
        # idcg: zero-gain padding contributes 0 at any position
        ideal = -np.sort(-gain, axis=1) / np.log2(np.arange(2, M + 2))[None, :]
        idcg = np.maximum(ideal.sum(axis=1), 1e-12)
        pm = m[:, :, None] & m[:, None, :]              # valid pair mask
        sd = s[:, :, None] - s[:, None, :]
        Sij = np.sign(yy[:, :, None] - yy[:, None, :])
        live = pm & (Sij != 0)
        with np.errstate(over="ignore"):
            rho = 1.0 / (1.0 + np.exp(sigma * sd * Sij))
        delta_ndcg = np.abs((gain[:, :, None] - gain[:, None, :])
                            * (disc[:, :, None] - disc[:, None, :])) \
            / idcg[:, None, None]
        gi = np.where(live, -sigma * rho * delta_ndcg * Sij, 0.0)
        hi = np.where(live, sigma * sigma * rho * (1 - rho) * delta_ndcg, 0.0)
        grow = gi.sum(axis=2)
        hrow = np.maximum(hi.sum(axis=2), 1e-9)
        multi = (cnt > 1)[:, None] & m                  # cnt<=1 groups stay 0
        g[ix[multi]] = grow[multi]
        h[ix[multi]] = hrow[multi]
    return g, h


class TrainConfig:
    def __init__(self, params: Dict, n_features: int):
        self.p = resolve_params(params)
        self.depth = _depth_for(self.p)
        self.n_features = n_features


class _PhaseProf:
    """Opt-in wall-clock phase breakdown (``MMLSPARK_TPU_GBDT_PROF=1``).

    ``mark`` blocks on the given arrays before reading the clock, so each
    phase's time includes its device work — profiling deliberately defeats
    async dispatch; production runs leave it off and pipeline.
    """

    def __init__(self):
        self.enabled = os.environ.get("MMLSPARK_TPU_GBDT_PROF", "0") == "1"
        self.t: Dict[str, float] = {}
        self._last = time.perf_counter()

    def mark(self, name: str, *sync):
        if not self.enabled:
            return
        for a in sync:
            # tpulint: disable=TPU001 — opt-in profiler: the fence IS the
            # measurement (off unless MMLSPARK_TPU_GBDT_PROF=1)
            jax.block_until_ready(a)
        now = time.perf_counter()
        self.t[name] = self.t.get(name, 0.0) + (now - self._last)
        _M_GBDT_PHASE.observe(now - self._last, phase=name)
        self._last = now

    def reset(self):
        if self.enabled:
            self._last = time.perf_counter()

    def report(self, n_iter: int):
        if self.enabled:
            print(json.dumps({"gbdt_phase_seconds":
                              {k: round(v, 3) for k, v in self.t.items()},
                              "n_iter": n_iter}), file=sys.stderr, flush=True)


def train(params: Dict,
          X: np.ndarray, y: np.ndarray,
          sample_weight: Optional[np.ndarray] = None,
          group: Optional[np.ndarray] = None,
          valid_sets: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None,
          init_model: Optional[Booster] = None,
          mesh: Optional[Mesh] = None,
          callbacks: Optional[List[Callable]] = None,
          eval_log: Optional[List] = None,
          init_score: Optional[np.ndarray] = None,
          valid_init_scores: Optional[List[np.ndarray]] = None,
          valid_weights: Optional[List[np.ndarray]] = None) -> Booster:
    """Fit a GBDT. ``params`` uses LightGBM names (aliases accepted).

    ``init_score``: per-row starting margin (LightGBM ``init_score``) —
    boosting fits residuals on top of it, and, as in LightGBM, the fitted
    model's predictions do NOT include it (the caller re-adds their margin
    at scoring time). With ``valid_sets``, matching per-set margins must
    come in ``valid_init_scores`` (each Dataset carries its own
    init_score in LightGBM too) so eval metrics are computed at the right
    margin. ``valid_weights``: per-set sample weights for eval metrics
    (LightGBM's Dataset weights apply to its eval too)."""
    p = resolve_params(params)
    # keep X in its incoming float width — a HIGGS-scale float32 matrix must
    # not be silently doubled to float64 (binning only ever copies a sample
    # and per-column temporaries); integers upcast to float64 so large ids
    # (> 2^24) stay distinct. scipy-sparse X stays sparse end-to-end: the
    # binned uint8 matrix is the only dense artifact (parity:
    # LGBM_DatasetCreateFromCSR, DatasetAggregator.scala:441-465)
    from .binning import is_sparse
    sparse_X = is_sparse(X)
    if sparse_X:
        X = X.tocsr()
        if X.dtype.kind != "f":
            X = X.astype(np.float64)
        if p["categorical_feature"]:
            raise ValueError(
                "categorical_feature is not supported with sparse input "
                "(rank-encode the categorical columns before sparsifying, "
                "or pass a dense matrix)")
    else:
        X = np.asarray(X)
        if X.dtype.kind != "f":
            X = X.astype(np.float64)
    y = np.asarray(y, dtype=np.float64)
    n, F = X.shape
    w = (np.asarray(sample_weight, dtype=np.float64) if sample_weight is not None
         else np.ones(n))
    depth = _depth_for(p)
    # single source of truth for "rows shard over a mesh" — consulted by
    # both the chunked-upload gate and the sharding setup below
    will_shard = (mesh is not None
                  and p["tree_learner"] in ("data_parallel",
                                            "voting_parallel"))
    num_class = int(p["num_class"])
    objective_name = p["objective"]
    # boosting mode (parity: LightGBMParams.boostingType, LightGBMParams.scala:389-393)
    boosting = {"gbrt": "gbdt", "random_forest": "rf"}.get(
        str(p["boosting"]).lower(), str(p["boosting"]).lower())
    if boosting not in ("gbdt", "goss", "dart", "rf"):
        raise ValueError(f"boosting must be gbdt/goss/dart/rf, got {boosting!r}")
    if boosting == "goss" and p["bagging_freq"]:
        raise ValueError("GOSS replaces bagging; unset bagging_freq")
    if boosting == "rf":
        if not (p["bagging_freq"] and 0 < float(p["bagging_fraction"]) < 1):
            raise ValueError("rf mode needs bagging_freq > 0 and "
                             "0 < bagging_fraction < 1 (LightGBM's own rule)")
        if p["early_stopping_round"]:
            raise ValueError("rf averages over the full planned forest; "
                             "early stopping would bias the average")
        if init_model is not None:
            raise ValueError("rf mode cannot warm-start (the 1/T average "
                             "is defined over one forest)")
    is_multi = objective_name in ("multiclass", "softmax") and num_class > 1
    is_rank = objective_name == "lambdarank"
    linear_tree = bool(p["linear_tree"])
    if linear_tree:
        # LightGBM linear_tree restrictions apply here too: leaf models
        # regress on raw numerical features only
        if sparse_X:
            raise ValueError("linear_tree needs dense input (the leaf "
                             "models regress on raw feature values)")
        if p["categorical_feature"]:
            raise ValueError("linear_tree regresses on numerical features "
                             "only; drop categorical_feature")
        if p["tree_learner"] == "voting_parallel":
            raise ValueError("linear_tree + voting_parallel is not "
                             "supported; use data_parallel")
        mc = p["monotone_constraints"]
        if mc is not None and np.asarray(mc).size and np.asarray(mc).any():
            # the split search could mask on constant child values, but the
            # fitted leaf ridge models are unclamped — predictions would
            # silently violate the declared direction (LightGBM refuses
            # this combination too)
            raise ValueError("linear_tree cannot honor "
                             "monotone_constraints; drop one of them")
        if float(p["lambda_l1"]) != 0.0:
            raise ValueError("lambda_l1 applies to constant leaf values "
                             "only; linear_tree leaves are L2-regularized "
                             "via linear_lambda (set lambda_l1=0)")
        if float(p["path_smooth"]) != 0.0:
            raise ValueError("path_smooth smooths constant leaf outputs; "
                             "it has no linear-leaf counterpart here "
                             "(set path_smooth=0)")
    obj = get_objective(objective_name, num_class=num_class,
                        alpha=p["alpha"],
                        tweedie_variance_power=p["tweedie_variance_power"])

    # class-imbalance reweighting (LightGBM scale_pos_weight/is_unbalance):
    # folded into the sample weights so gradients, hessians, and
    # boost-from-average all see it consistently
    spw = float(p["scale_pos_weight"])
    if p["is_unbalance"] or spw != 1.0:
        if objective_name != "binary":
            raise ValueError("scale_pos_weight/is_unbalance apply to the "
                             "binary objective only")
        if p["is_unbalance"]:
            if spw != 1.0:
                raise ValueError("set either is_unbalance or "
                                 "scale_pos_weight, not both (LightGBM's "
                                 "own rule)")
            pos = float(np.sum(w * (y == 1)))
            neg = float(np.sum(w * (y != 1)))
            if pos <= 0.0:
                raise ValueError(
                    "is_unbalance: no positive examples (or zero positive "
                    "weight) — the auto ratio would be unbounded")
            spw = neg / pos
        w = w * np.where(y == 1, spw, 1.0)

    # step-level checkpoint/resume (beyond the reference's model-level
    # warm start): a run killed mid-training resumes from the last step
    ckpt = None
    resumed_iters = 0
    if p["checkpoint_dir"] and init_score is not None:
        # checkpoints persist only the booster; a resume could not
        # reconstruct the margin-adjusted score state
        raise ValueError("init_score cannot combine with step checkpoints")
    if p["checkpoint_dir"]:
        from ...utils.checkpoint import TrainingCheckpointer
        ckpt = TrainingCheckpointer(str(p["checkpoint_dir"]))
        latest = ckpt.latest()
        if latest is not None:
            _, files = latest
            meta = TrainingCheckpointer.read_json(files["meta.json"])
            resumed_iters = int(meta["completed_iterations"])
            init_model = Booster.from_string(
                TrainingCheckpointer.read_text(files["booster.txt"]))

    X_raw = X
    cat_encoder = None
    if p["categorical_feature"] or (init_model is not None
                                    and init_model.cat_encoder is not None):
        # label-ordered rank encoding (categorical.py): the static
        # approximation of LightGBM's per-node category-subset search;
        # warm starts reuse the prior booster's encoding (its trees split
        # in that rank space)
        from .categorical import CategoricalEncoder
        if sparse_X:
            raise ValueError("categorical encoding and sparse input cannot "
                             "combine (the warm-start model was trained "
                             "with categorical_feature)")
        if init_model is not None and init_model.cat_encoder is not None:
            cat_encoder = init_model.cat_encoder
        elif init_model is not None:
            # the init model's trees split raw values; appending trees that
            # split rank-encoded values would mix spaces undetectably
            raise ValueError(
                "categorical_feature set, but the warm-start model was "
                "trained without categorical encoding; retrain from "
                "scratch or drop categorical_feature")
        else:
            cat_encoder = CategoricalEncoder(
                p["categorical_feature"],
                cat_smooth=float(p["cat_smooth"]),
                min_data_per_group=int(p["min_data_per_group"])).fit(X, y)
        X = cat_encoder.transform(X)

    prof = _PhaseProf()
    prof.reset()
    mapper = BinMapper(max_bin=int(p["max_bin"]), seed=int(p["seed"]))
    bundle_tables = None
    n_bundle_bins = 0
    if sparse_X and p["enable_bundle"]:
        # EFB: mutually-exclusive sparse features share histogram columns
        # (LightGBM enable_bundle/max_conflict_rate); per-level histogram
        # passes and bin-matrix bytes shrink from F to n_bundles columns
        # (total bins — and the psum payload — stay ≈ constant)
        from .bundling import FeatureBundler
        from .trees import BundleTables
        mapper.fit(X)
        bundler = FeatureBundler(
            max_conflict_rate=float(p["max_conflict_rate"]),
            max_bundle_bins=int(p["max_bundle_bins"])).fit(X, mapper)
        if bundler.worthwhile(F):
            xb = bundler.transform(X, mapper)
            bundle_tables = BundleTables(
                jnp.asarray(bundler.bundle_of),
                jnp.asarray(bundler.offset_of),
                jnp.asarray(bundler.width_of),
                jnp.asarray(bundler.zero_bin))
            n_bundle_bins = bundler.n_bundle_bins
        else:
            xb = mapper.transform(X)
    else:
        mapper.fit(X)
        prof.mark("bin_fit")
        if not will_shard and not sparse_X and n >= (1 << 21):
            # chunked bin→upload pipeline: while chunk i transfers (async
            # device_put), chunk i+1 bins on the host — at HIGGS scale this
            # hides most of the h2d time behind the native binning loop,
            # and the full host-side binned matrix never materializes
            CHR = 1 << 21
            # tpulint: disable=TPU021 — single-device branch by
            # construction (``not will_shard`` above): the chunked upload
            # stages bins on the default device; the mesh path device_puts
            # rows under NamedSharding(mesh, P("data")) (row_sharding)
            parts = [jax.device_put(mapper.transform(X[lo:lo + CHR]))
                     for lo in range(0, n, CHR)]
            xb_dev_early = (jnp.concatenate(parts, axis=0)
                            if len(parts) > 1 else parts[0])
            xb = None
            prof.mark("bin_upload_overlap", xb_dev_early)
        else:
            xb = mapper.transform(X)
            prof.mark("bin_transform")
    n_bins = mapper.n_bins

    if init_model is not None and init_score is not None:
        raise ValueError("init_score cannot combine with a warm-start "
                         "model (the model already defines the margin)")
    if init_model is not None \
            and getattr(init_model, "is_linear", False) != linear_tree:
        raise ValueError("warm start must keep the leaf model family: "
                         "set linear_tree to match the init model")
    if init_model is not None:
        # dart mutates leaf values in place (scale_trees) — work on a deep
        # copy so the caller's model object is never changed under them
        booster = (init_model.truncated(init_model.num_trees)
                   if boosting == "dart" else init_model)
        base_score = booster.base_score
        # raw_score applies the encoder itself — feed the UN-encoded matrix
        # (sparse passes through; raw_score densifies in bounded chunks)
        scores = booster.raw_score(
            X_raw if X_raw.dtype == np.float32 else X_raw.astype(np.float32)
        ) - np.float32(base_score)
        init_trees = booster.num_trees
        init_arr = None
    else:
        init_trees = 0
        if init_score is not None:
            # per-row starting margin: boost-from-average is skipped
            # (LightGBM semantics) and predictions exclude the margin
            init_arr = np.asarray(init_score, dtype=np.float64)
            want = (n, num_class) if is_multi else (n,)
            if init_arr.shape != want:
                raise ValueError(f"init_score shape {init_arr.shape} != "
                                 f"{want}")
            base_score = 0.0
            scores = init_arr.copy()
        else:
            init_arr = None
            # LightGBM boost_from_average: the first margin is the
            # objective's optimal constant; off → boosting starts at 0
            base_score = 0.0 if (is_multi or is_rank
                                 or not p["boost_from_average"]) \
                else obj.init_score(y, w)
            scores = np.zeros((n, num_class) if is_multi else n)
        booster = Booster(depth, F, objective_name, base_score,
                          num_class if is_multi else 1)
        booster.cat_encoder = cat_encoder

    # device residency; shard rows when data-parallel over a mesh
    axis_name = None
    n_pad = n
    if will_shard:
        axis_name = "data"
        shards = mesh.shape[axis_name]
        n_pad = ((n + shards - 1) // shards) * shards
        row_sharding = NamedSharding(mesh, P("data"))
    if n_pad != n:
        pad = n_pad - n
        xb = np.concatenate([xb, np.zeros((pad, xb.shape[1]),
                                          dtype=xb.dtype)])
        y_pad = np.concatenate([y, np.zeros(pad)])
        w_pad = np.concatenate([w, np.zeros(pad)])
        scores = np.concatenate(
            [scores, np.zeros((pad,) + scores.shape[1:])], axis=0)
    else:
        y_pad, w_pad = y, w
    live = np.concatenate([np.ones(n), np.zeros(n_pad - n)])

    # scores live on device between iterations as the DELTA from
    # base_score: a host round-trip of the full score vector every iteration
    # dominates training at HIGGS scale, and centering keeps
    # f32 accumulation exact-ish (leaf deltas are small; adding them into a
    # large absolute base like mean(y)~1e3 would round at ~6e-5 ULP each
    # iteration). grad inputs re-add base_score on device.
    init_pad = None
    if init_arr is not None:
        ip = (np.concatenate([init_arr,
                              np.zeros((n_pad - n,) + init_arr.shape[1:])])
              if n_pad != n else init_arr)
        init_pad = jnp.asarray(ip, jnp.float32)
        if axis_name is not None:
            init_pad = jax.device_put(init_pad, row_sharding)
    scores = jnp.asarray(scores, jnp.float32)
    if axis_name is not None:
        scores = jax.device_put(scores, row_sharding)
        xb_d = jax.device_put(jnp.asarray(xb), row_sharding)
        y_d = jax.device_put(jnp.asarray(y_pad), row_sharding)
        w_d = jax.device_put(jnp.asarray(w_pad), row_sharding)
        live_d = jax.device_put(jnp.asarray(live), row_sharding)
    else:
        xb_d = xb_dev_early if xb is None else jnp.asarray(xb)
        y_d = jnp.asarray(y_pad)
        w_d = jnp.asarray(w_pad)
        live_d = jnp.asarray(live)
    prof.mark("upload", xb_d, y_d, w_d, live_d, scores)

    # kernel lane layout, once per RUN (the per-level transpose it replaces
    # cost a full read+write of the bin matrix each level of each tree)
    xb_lanes_d = None
    if axis_name is None:
        from ...ops.pallas_kernels import (histogram_enabled,
                                           pallas_preferred,
                                           prepare_bins_lanes,
                                           tree_row_block)
        kbins = int(n_bundle_bins) if n_bundle_bins else int(n_bins)
        if histogram_enabled() and pallas_preferred(
                n_pad, 2 ** max(depth - 1, 0), kbins):
            # row block must match build_tree's tree_row_block choice (the
            # kernel validates npad divisibility against it)
            xb_lanes_d = prepare_bins_lanes(
                xb_d, row_block=tree_row_block(2 ** max(depth - 1, 0),
                                               kbins))

    X_lin = None
    if linear_tree:
        # linear leaves regress on RAW values — the binned matrix loses
        # them, so the float32 feature matrix also lives on device
        xf = np.asarray(X, dtype=np.float32)
        if n_pad != n:
            xf = np.concatenate(
                [xf, np.zeros((n_pad - n, F), np.float32)])
        X_lin = jnp.asarray(xf)
        if axis_name is not None:
            X_lin = jax.device_put(X_lin, row_sharding)

    # PV-Tree voting (LightGBM tree_learner=voting_parallel, topK param —
    # params/LightGBMParams.scala:23-30): comm per level 2k×B instead of F×B
    voting_k = (int(p["top_k"]) if p["tree_learner"] == "voting_parallel"
                else 0)
    ffbn = float(p["feature_fraction_bynode"])
    if not 0.0 < ffbn <= 1.0:
        raise ValueError(f"feature_fraction_bynode must be in (0, 1], "
                         f"got {ffbn}")
    if float(p["path_smooth"]) < 0.0:
        raise ValueError("path_smooth must be >= 0")
    build_kwargs = dict(depth=depth, n_bins=int(n_bins),
                        voting_k=voting_k,
                        lam=float(p["lambda_l2"]) + 1e-10,
                        alpha=float(p["lambda_l1"]),
                        min_gain=float(p["min_gain_to_split"]),
                        min_child_weight=float(p["min_sum_hessian_in_leaf"]),
                        min_data_in_leaf=float(p["min_data_in_leaf"]),
                        bundles=bundle_tables,
                        n_bundle_bins=int(n_bundle_bins),
                        extra_trees=bool(p["extra_trees"]),
                        ff_bynode=ffbn,
                        path_smooth=float(p["path_smooth"]),
                        hist_dtype=("bfloat16" if p["use_quantized_grad"]
                                    else None))
    if p["extra_trees"]:
        # per-feature populated bin counts (incl. missing bin 0): the
        # random-threshold draw samples each feature's own range
        build_kwargs["feat_bins"] = jnp.asarray(
            [len(b) + 1 for b in mapper.upper_bounds], jnp.int32)
    ic_raw = p["interaction_constraints"]
    if ic_raw:
        # list of allowed feature groups; a branch may only combine
        # features that share at least one group, and features in no
        # group are unusable (LightGBM interaction_constraints semantics)
        groups = np.zeros((len(ic_raw), F), dtype=bool)
        for gi, grp in enumerate(ic_raw):
            idx = np.asarray(list(grp), dtype=np.int64)
            if idx.size == 0:
                raise ValueError("interaction_constraints groups must be "
                                 "non-empty")
            if idx.min() < 0 or idx.max() >= F:
                raise ValueError(
                    f"interaction_constraints[{gi}] has feature indices "
                    f"outside [0, {F})")
            groups[gi, idx] = True
        build_kwargs["ic_groups"] = jnp.asarray(groups)

    mono_raw = p["monotone_constraints"]
    if mono_raw is not None and np.asarray(mono_raw).size:
        # validate RAW values before the int cast (int32 would silently
        # zero fractional entries — a vacuous constraint, not an error)
        raw = np.asarray(mono_raw)
        if raw.shape != (F,):
            raise ValueError(
                f"monotone_constraints needs one entry per feature "
                f"({F}), got shape {raw.shape}")
        if not np.isin(raw, (-1, 0, 1)).all():
            raise ValueError("monotone_constraints entries must be "
                             "-1, 0, or +1")
        mono = raw.astype(np.int32)
        if cat_encoder is not None:
            cat_set = set(cat_encoder.feature_indices)
            cat_idx = [int(i) for i in np.nonzero(mono)[0]
                       if int(i) in cat_set]
            if cat_idx:
                # the encoder rewrites these columns to label-ordered
                # ranks; a "monotone in the raw value" promise would be
                # silently vacuous (LightGBM rejects this combination too)
                raise ValueError(
                    f"monotone_constraints on categorical features "
                    f"{cat_idx} are not supported")
        if mono.any():
            build_kwargs["monotone"] = jnp.asarray(mono)

    if axis_name is None:
        def build(xb_, g_, h_, live_, fmask, key, lanes=None):
            # lanes passed as an ARG (not closed over): a closure-captured
            # device array would be baked into the jitted program as a
            # constant
            return build_tree(xb_, g_, h_, live_, feature_mask=fmask,
                              rng=key, xb_lanes=lanes, **build_kwargs)
    else:
        n_int = 2 ** depth - 1

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("data", None), P("data"), P("data"), P("data"),
                      P(None), P(None)),
            out_specs=(P(None), P(None), P(None), P("data"), P(None), P(None)),
            check_vma=False)
        def _build_sharded(xb_, g_, h_, live_, fmask, key):
            # key replicated: every shard draws identical random masks, so
            # extra_trees/by-node sampling stays bitwise-deterministic
            # across the mesh (same invariant as the psum'd histogram)
            return build_tree(xb_, g_, h_, live_, feature_mask=fmask,
                              rng=key, axis_name=axis_name, **build_kwargs)

        def build(xb_, g_, h_, live_, fmask, key, lanes=None):
            # per-shard lane layouts are prepared inside build_tree (once
            # per tree); a replicated global layout is ignored here
            return _build_sharded(xb_, g_, h_, live_, fmask, key)

    lin_fit = None
    if linear_tree:
        from .trees import fit_linear_leaves
        lin_kwargs = dict(n_leaf=2 ** depth,
                          lam_lin=float(p["linear_lambda"]),
                          lam=float(p["lambda_l2"]) + 1e-10)
        if axis_name is None:
            def lin_fit(Xr, li, g_, h_, live_, pf):
                return fit_linear_leaves(Xr, li, g_, h_, live_, pf,
                                         **lin_kwargs)
        else:
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P("data", None), P("data"), P("data"), P("data"),
                          P("data"), P(None)),
                out_specs=(P(None), P("data")), check_vma=False)
            def lin_fit(Xr, li, g_, h_, live_, pf):
                # normal equations psum inside, so coefficients are
                # identical on every shard (bitwise-deterministic like
                # the histogram path)
                return fit_linear_leaves(Xr, li, g_, h_, live_, pf,
                                         axis_name=axis_name, **lin_kwargs)

    def _pred_stack(feats_a, thr_a, leaf_a, Xq, coefs_a=None, pf_a=None):
        """Tree-stack prediction, constant or linear leaves."""
        from .trees import (predict_trees_any, predict_trees_linear_any,
                            predict_trees_linear_multi_any)
        if linear_tree:
            if is_multi:
                # class-major tree order (t % K) holds for every stack
                # this sees: full prefixes, one-iteration groups, dart's
                # whole-group drops
                return predict_trees_linear_multi_any(
                    feats_a, thr_a, coefs_a, pf_a, Xq, depth=depth,
                    num_class=num_class)
            return predict_trees_linear_any(feats_a, thr_a, coefs_a, pf_a,
                                            Xq, depth=depth)
        return predict_trees_any(feats_a, thr_a, leaf_a, Xq, depth=depth)

    booster.fit_params = {"learning_rate": float(p["learning_rate"]),
                          "lambda_l2": float(p["lambda_l2"])}
    grad_fn = jax.jit(obj.grad_hess) if obj.grad_hess is not None else None
    lr = float(p["learning_rate"])
    rng = np.random.default_rng(int(p["seed"]))
    base_key = jax.random.PRNGKey(int(p["seed"]))
    n_iter = max(0, int(p["num_iterations"]) - resumed_iters)
    ckpt_iv = int(p["checkpoint_interval"]) if ckpt is not None else 0

    # eval bookkeeping. LightGBM accepts a METRIC LIST: every metric is
    # computed and logged per iteration; early stopping follows the FIRST
    # (LightGBM's first_metric_only=True discipline — the stable subset of
    # its any-metric default, which couples the stop decision to list
    # order anyway)
    m_raw = p["metric"]
    metric_list = (list(m_raw) if isinstance(m_raw, (list, tuple))
                   else [m_raw])
    if not metric_list:
        metric_list = ["auto"]      # empty list = objective default (LGBM)
    resolved = [get_metric(m if m not in ("auto", "") else "",
                           objective_name) for m in metric_list]
    metric_name, (metric_fn, higher_better) = resolved[0]
    best_score = -np.inf if higher_better else np.inf
    best_iter = 0
    best_model = None               # dart: snapshot at each new best
    patience = int(p["early_stopping_round"])
    valid_scores = None
    if valid_sets:
        valid_sets = [(vx if is_sparse(vx) else np.asarray(vx), vy)
                      for vx, vy in valid_sets]
        if init_score is not None and valid_init_scores is None:
            raise ValueError(
                "init_score with valid_sets needs valid_init_scores "
                "(one margin array per validation set) — eval at margin "
                "zero would select a wrong best_iteration")
        if init_trees:
            valid_scores = [booster.raw_score(
                vx if is_sparse(vx) else np.asarray(vx, dtype=np.float32))
                .astype(np.float64) for vx, _vy in valid_sets]
        else:
            valid_scores = [np.full(
                (vx.shape[0], num_class) if is_multi else vx.shape[0],
                base_score, dtype=np.float64) for vx, _vy in valid_sets]
        if valid_weights is not None:
            if len(valid_weights) != len(valid_sets):
                raise ValueError(
                    f"valid_weights has {len(valid_weights)} entries for "
                    f"{len(valid_sets)} valid_sets")
            valid_weights = [np.asarray(w, dtype=np.float64)
                             for w in valid_weights]
            for vi, (w_, (vx_, _vy)) in enumerate(
                    zip(valid_weights, valid_sets)):
                if len(w_) != vx_.shape[0]:
                    raise ValueError(
                        f"valid_weights[{vi}] has {len(w_)} rows for a "
                        f"{vx_.shape[0]}-row validation set")
        valid_margins = None
        if valid_init_scores is not None:
            if len(valid_init_scores) != len(valid_sets):
                raise ValueError(
                    f"valid_init_scores has {len(valid_init_scores)} "
                    f"entries for {len(valid_sets)} valid_sets")
            valid_margins = []
            for vi, vis in enumerate(valid_init_scores):
                vis = np.asarray(vis, dtype=np.float64)
                if vis.shape != valid_scores[vi].shape:
                    raise ValueError(
                        f"valid_init_scores[{vi}] shape {vis.shape} != "
                        f"{valid_scores[vi].shape}")
                valid_margins.append(vis)
                valid_scores[vi] = valid_scores[vi] + vis
        if cat_encoder is not None:
            # the per-iteration eval path feeds trees directly (bypassing
            # booster.raw_score), so hand it rank-encoded matrices once
            if any(is_sparse(vx) for vx, _ in valid_sets):
                raise ValueError("sparse validation sets cannot combine "
                                 "with categorical_feature")
            valid_sets = [(cat_encoder.transform(np.asarray(vx)), vy)
                          for vx, vy in valid_sets]

    X_f32 = ((X.astype(np.float32) if sparse_X
              else np.asarray(X, dtype=np.float32))
             if boosting == "dart" else None)
    rf_scale = 1.0 / max(1, int(p["num_iterations"])) if boosting == "rf" \
        else None
    K_trees = num_class if is_multi else 1

    # -- fused/deferred fast path -------------------------------------------
    # For the plain-gbdt configuration (the HIGGS north-star shape) the whole
    # iteration — gradients, masking, tree build, score update — is ONE
    # jitted dispatch, and the fitted tree arrays stay on device until after
    # the loop. The Python loop then never blocks: iterations pipeline
    # back-to-back on the chip and per-dispatch/transfer round-trips
    # amortize away, where the materializing path
    # paid ~5 of them per iteration. Excluded modes keep the general path:
    # goss (host top-k), dart (host drop bookkeeping), rf (constant-margin
    # grads), lambdarank (host pairwise grads), multiclass (vmap build),
    # linear_tree (host path_features), eval/callback/checkpoint consumers
    # (need the booster per iteration).
    defer = (boosting == "gbdt" and not is_rank and not is_multi
             and not linear_tree and not valid_sets and not callbacks
             and ckpt is None and grad_fn is not None)
    fused_step = None
    if defer:
        lr_fast = lr     # gbdt: tree_scale == 1.0 always

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fused_step(scores_, xb_, y_, w_, gh_w_, live_it_, fmask_, key_,
                       lanes_):
            g_, h_ = obj.grad_hess(scores_ + jnp.float32(base_score),
                                   y_, w_)
            # gh_w always carries the live-row factor (it is live_d or a
            # bagged subset of it), so one multiply applies both masks
            g_ = g_ * gh_w_
            h_ = h_ * gh_w_
            feats_, thr_, leaf_, node_, gains_, covers_ = build(
                xb_, g_, h_, live_it_, fmask_, key_, lanes_)
            scores2 = scores_ + jnp.take(leaf_, node_) * lr_fast
            return scores2, feats_, thr_, leaf_, gains_, covers_

    pending: List[Tuple] = []
    fmask_all = jnp.ones(F, dtype=bool)     # hoisted: constant across iters

    def _bagging_masks(it):
        """(live_it, gh_w) for this iteration. Shared by the fused and the
        general loop paths so the rng stream stays in lockstep — a given
        seed must yield identical row subsets either way."""
        if p["bagging_freq"] and p["bagging_fraction"] < 1.0 \
                and it % int(p["bagging_freq"]) == 0:
            keep = rng.random(n_pad) < float(p["bagging_fraction"])
            live_it = live_d * jnp.asarray(keep.astype(np.float64))
            return live_it, live_it
        return live_d, live_d

    def _feature_mask():
        """Per-tree feature subsample mask (same rng-lockstep contract)."""
        if float(p["feature_fraction"]) < 1.0:
            k = max(1, int(round(F * float(p["feature_fraction"]))))
            sel = rng.choice(F, size=k, replace=False)
            m = np.zeros(F, dtype=bool)
            m[sel] = True
            return jnp.asarray(m)
        return fmask_all

    for it in range(n_iter):
        prof.reset()
        if defer:
            # one fused dispatch; tree arrays stay on device (materialized
            # in one batch after the loop)
            live_it, gh_w = _bagging_masks(it)
            fmask = _feature_mask()
            it_key = jax.random.fold_in(base_key, resumed_iters + it)
            scores, feats, thr_bin, leaf_val, gains, covers = fused_step(
                scores, xb_d, y_d, w_d, gh_w, live_it, fmask, it_key,
                xb_lanes_d)
            pending.append((feats, thr_bin, leaf_val, gains, covers))
            prof.mark("fused_step", scores)
            continue
        # -- dart: pick an iteration subset to drop, score without it ------
        drop_idx = None
        drop_pred = None
        tree_scale = 1.0
        if boosting == "dart":
            n_groups = booster.num_trees // K_trees
            drop_groups = np.array([], dtype=np.int64)
            if n_groups and rng.random() >= float(p["skip_drop"]):
                cand = np.nonzero(rng.random(n_groups)
                                  < float(p["drop_rate"]))[0]
                md = int(p["max_drop"])
                if md > 0 and len(cand) > md:
                    cand = np.sort(rng.choice(cand, size=md, replace=False))
                drop_groups = cand
            if len(drop_groups):
                k_drop = len(drop_groups)
                tree_scale = 1.0 / (k_drop + 1.0)   # DART-paper weights
                drop_idx = (drop_groups[:, None] * K_trees
                            + np.arange(K_trees)[None, :]).ravel()
                lin = booster.linear if linear_tree else None
                dp = _pred_stack(
                    booster.feats[drop_idx], booster.thr_raw[drop_idx],
                    booster.leaf_values[drop_idx], X_f32,
                    coefs_a=lin["coefs"][drop_idx] if lin else None,
                    pf_a=lin["pf"][drop_idx] if lin else None)
                drop_pred = jnp.pad(
                    dp, ((0, n_pad - n),) + ((0, 0),) * (dp.ndim - 1))
                if axis_name is not None:
                    # dp is committed to one device by predict_trees; the
                    # subtraction partner is mesh-sharded
                    drop_pred = jax.device_put(drop_pred, row_sharding)
        elif boosting == "rf":
            tree_scale = rf_scale

        # trees fit gradients at: scores minus dropped trees (dart), the
        # constant init score (rf: every tree fits the same residual and
        # the 1/T-scaled sum is the forest average), else current scores
        scores_for_grad = scores + jnp.float32(base_score)
        if drop_pred is not None:
            scores_for_grad = scores_for_grad - drop_pred
        elif boosting == "rf":
            # rf: every tree fits the same residual — at the per-row margin
            # when init_score was given, else at the constant init score
            scores_for_grad = (init_pad if init_pad is not None
                               else jnp.full_like(scores, base_score))

        # gradients
        if is_rank:
            g_np, h_np = _lambdarank_grad(
                np.asarray(scores_for_grad[:n], dtype=np.float64), y, group)
            g_np, h_np = g_np * w, h_np * w
            if n_pad != n:
                g_np = np.concatenate([g_np, np.zeros(n_pad - n)])
                h_np = np.concatenate([h_np, np.zeros(n_pad - n)])
            g_d, h_d = jnp.asarray(g_np), jnp.asarray(h_np)
            if axis_name is not None:
                g_d = jax.device_put(g_d, row_sharding)
                h_d = jax.device_put(h_d, row_sharding)
        else:
            g_d, h_d = grad_fn(scores_for_grad, y_d, w_d)
            g_d = g_d * live_d[..., None] if is_multi else g_d * live_d
            h_d = h_d * live_d[..., None] if is_multi else h_d * live_d
        prof.mark("grad", g_d, h_d)

        # goss / bagging / feature sampling. ``live_it`` is the 0/1 row
        # membership (drives min_data_in_leaf counts and stored covers);
        # ``gh_w`` additionally carries GOSS's gradient amplification —
        # LightGBM amplifies only grad/hess, never the count channel
        if boosting == "goss":
            # gradient-based one-side sampling: keep the top_rate fraction
            # by |grad|, sample other_rate of the rest amplified by
            # (1-a)/b so the small-gradient mass stays unbiased
            g_host = np.asarray(g_d)[:n]
            gabs = (np.abs(g_host).sum(axis=1) if is_multi
                    else np.abs(g_host))
            a, b = float(p["top_rate"]), float(p["other_rate"])
            top_n = min(n, max(1, int(math.ceil(a * n))))
            rest_n = max(0, int(math.ceil(b * n)))
            order = np.argpartition(-gabs, top_n - 1)
            sel_bin = np.zeros(n_pad)
            sel_amp = np.zeros(n_pad)
            sel_bin[order[:top_n]] = 1.0
            sel_amp[order[:top_n]] = 1.0
            rest = order[top_n:]
            if rest_n and len(rest):
                samp = rng.choice(rest, size=min(rest_n, len(rest)),
                                  replace=False)
                sel_bin[samp] = 1.0
                sel_amp[samp] = (1.0 - a) / max(b, 1e-12)
            live_it = live_d * jnp.asarray(sel_bin)
            gh_w = live_d * jnp.asarray(sel_amp)
        else:
            live_it, gh_w = _bagging_masks(it)
        fmask = _feature_mask()
        mask_g = gh_w if not is_multi else gh_w[:, None]
        # rf has no shrinkage — each tree enters at 1/T so the sum is the
        # forest average; dart additionally scales the new tree by 1/(k+1)
        lr_eff = (1.0 if boosting == "rf" else lr) * tree_scale

        it_key = jax.random.fold_in(base_key, resumed_iters + it)
        new_coefs = new_pf = None
        if is_multi:
            g_mk = g_d * mask_g
            h_mk = h_d * mask_g

            def build_k(gk, hk, kk):
                return build(xb_d, gk, hk, live_it, fmask, kk)
            feats_k, thr_k, leaf_k, node_k, gains_k, covers_k = jax.vmap(
                build_k, in_axes=(1, 1, 0))(
                    g_mk, h_mk, jax.random.split(it_key, num_class))
            feats_np = np.asarray(feats_k)      # (K, n_int)
            thr_raw_k = np.stack([
                _thr_bins_to_raw(feats_np[k], np.asarray(thr_k)[k], mapper,
                                 int(n_bins)) for k in range(num_class)])
            if linear_tree:
                # per-class linear leaves: each class's tree fits its own
                # leaf ridge models on that class's gradients; trees stay
                # class-major so t % K routes predictions (trees.py
                # predict_trees_linear_multi_any)
                from .trees import path_features
                pf_k = np.stack([path_features(feats_np[k], depth)
                                 for k in range(num_class)])
                coefs_list, contrib_cols = [], []
                for k in range(num_class):
                    beta, contrib = lin_fit(X_lin, node_k[k], g_mk[:, k],
                                            h_mk[:, k], live_it,
                                            jnp.asarray(pf_k[k]))
                    coefs_list.append(
                        np.asarray(beta, np.float32) * np.float32(lr_eff))
                    contrib_cols.append(contrib)
                coefs_k = np.stack(coefs_list)       # (K, n_leaf, D+1)
                # per-class leaf value view: the coefs' bias (constant
                # fallback) for linear leaves
                vals_k = coefs_k[:, :, -1]
                scores = scores + jnp.stack(contrib_cols, axis=1) * lr_eff
                new_coefs = coefs_k
                new_pf = pf_k
            else:
                vals_k = np.asarray(leaf_k) * lr_eff
                # score update via leaf assignment, on device
                upd = jax.vmap(jnp.take)(leaf_k, node_k).T * lr_eff
                scores = scores + upd
            for k in range(num_class):
                lv = np.zeros((num_class, 2 ** depth), dtype=np.float32)
                lv[k] = vals_k[k]
                booster.append_tree(
                    feats_np[k], thr_raw_k[k], lv,
                    np.asarray(gains_k)[k], np.asarray(covers_k)[k],
                    **(dict(coefs=coefs_k[k], pf=pf_k[k])
                       if linear_tree else {}))
            new_feats = feats_np
            new_thr = thr_raw_k
            new_leaf = np.stack([
                np.eye(num_class, dtype=np.float32)[k][:, None]
                * np.asarray(vals_k[k])[None, :] for k in range(num_class)])
        else:
            g_m = g_d * gh_w
            h_m = h_d * gh_w
            feats, thr_bin, leaf_val, node_rel, gains, covers = build(
                xb_d, g_m, h_m, live_it, fmask, it_key, xb_lanes_d)
            prof.mark("build", feats, leaf_val, node_rel)
            feats_np = np.asarray(feats)
            thr_raw = _thr_bins_to_raw(feats_np, np.asarray(thr_bin), mapper,
                                       int(n_bins))
            if linear_tree:
                from .trees import path_features
                pf_np = path_features(feats_np, depth)
                beta, contrib = lin_fit(X_lin, node_rel, g_m, h_m, live_it,
                                        jnp.asarray(pf_np))
                coefs_np = np.asarray(beta, np.float32) * np.float32(lr_eff)
                # leaf_values keep the bias (the constant-fallback view)
                leaf_np = coefs_np[:, -1].copy()
                booster.append_tree(feats_np, thr_raw, leaf_np,
                                    np.asarray(gains), np.asarray(covers),
                                    coefs=coefs_np, pf=pf_np)
                scores = scores + contrib * lr_eff
                new_coefs = coefs_np[None]
                new_pf = pf_np[None]
            else:
                leaf_np = np.asarray(leaf_val) * lr_eff
                booster.append_tree(feats_np, thr_raw, leaf_np,
                                    np.asarray(gains), np.asarray(covers))
                prof.mark("host_tree")
                scores = scores + jnp.take(leaf_val, node_rel) * lr_eff
                prof.mark("score_update", scores)
            new_feats = feats_np[None]
            new_thr = thr_raw[None]
            new_leaf = leaf_np[None]

        if drop_idx is not None:
            # dart normalization: dropped trees re-enter at k/(k+1); the
            # running scores still hold them at full weight, so pull the
            # 1/(k+1) difference back out (grad was taken at scores - drop)
            k_drop = len(drop_idx) // K_trees
            booster.scale_trees(drop_idx, k_drop * tree_scale)
            scores = scores - drop_pred * tree_scale

        # eval + early stopping (uses this iteration's trees directly so the
        # booster's lazy tree stack is not re-materialized every round)
        if valid_sets:
            results = []
            per_set_log = (eval_log is not None
                           and (len(resolved) > 1 or len(valid_sets) > 1))
            for vi, (vx, vy) in enumerate(valid_sets):
                if drop_idx is not None:
                    # past trees were just re-scaled (dart drop) —
                    # incremental tracking is invalid for this round,
                    # recompute from the full tree stack; no-drop rounds
                    # keep the O(1)-tree incremental path
                    lin = booster.linear if linear_tree else None
                    valid_scores[vi] = base_score + _pred_stack(
                        booster.feats, booster.thr_raw, booster.leaf_values,
                        vx, coefs_a=lin["coefs"] if lin else None,
                        pf_a=lin["pf"] if lin else None)
                    if valid_margins is not None:
                        valid_scores[vi] = valid_scores[vi] \
                            + valid_margins[vi]
                else:
                    delta = _pred_stack(new_feats, new_thr, new_leaf, vx,
                                        coefs_a=new_coefs, pf_a=new_pf)
                    valid_scores[vi] = valid_scores[vi] + delta
                pred = np.asarray(obj.transform(jnp.asarray(valid_scores[vi])))
                vw = (valid_weights[vi] if valid_weights is not None
                      else np.ones(len(vy)))
                vy_arr = np.asarray(vy)
                # non-primary metrics only cost compute when something
                # consumes them (the per-set log)
                use = resolved if per_set_log else resolved[:1]
                vals = {mname: mfn(vy_arr, pred, vw)
                        for mname, (mfn, _hb) in use}
                results.append(vals[metric_name])
                if per_set_log:
                    for mname, mv in vals.items():
                        eval_log.append({"iteration": it, "valid_set": vi,
                                         mname: mv})
            primary = results[0]
            if eval_log is not None:
                # tagged so consumers can tell the early-stopping summary
                # from the self-describing per-set entries (which repeat
                # this value for set 0 when per_set_log is on)
                entry = {"iteration": it, metric_name: primary}
                if per_set_log:
                    entry["primary"] = True
                eval_log.append(entry)
            improved = primary > best_score if higher_better else primary < best_score
            if improved:
                best_score = primary
                best_iter = it + 1
                if boosting == "dart":
                    # later drop iterations rescale EARLIER trees in place,
                    # so a truncation taken at patience time would not be
                    # the model that scored best — snapshot it now
                    # (truncated() copies arrays)
                    best_model = booster.truncated(
                        init_trees + best_iter * K_trees)
            elif patience and (it + 1 - best_iter) >= patience:
                booster.best_iteration = best_iter
                final = (best_model if best_model is not None
                         else booster.truncated(
                             init_trees + best_iter * K_trees))
                if ckpt is not None:
                    # mark the run complete (full budget) so an idempotent
                    # rerun returns this truncated booster, not a resumed one
                    ckpt.save(int(p["num_iterations"]), {
                        "booster.txt": final.to_string(),
                        "meta.json": {"completed_iterations":
                                      int(p["num_iterations"])},
                    })
                return final
        if callbacks:
            scores_np = np.asarray(scores, dtype=np.float64) + base_score
            for cb in callbacks:
                cb(it, booster, scores_np)
        if ckpt_iv and (it + 1) % ckpt_iv == 0:
            ckpt.save(resumed_iters + it + 1, {
                "booster.txt": booster.to_string(),
                "meta.json": {"completed_iterations": resumed_iters + it + 1},
            })

    if pending:
        # materialize the deferred device-side tree stack: stack in chunks
        # (bounding trace size), one host transfer per chunk instead of ~5
        # per iteration, then one vectorized bin→raw threshold conversion
        CH = 64
        cols = [[], [], [], [], []]
        for lo in range(0, len(pending), CH):
            grp = pending[lo:lo + CH]
            for i in range(5):
                cols[i].append(np.asarray(jnp.stack([t[i] for t in grp])))
        feats_all, thr_all, leaf_all, gains_all, covers_all = (
            np.concatenate(c) for c in cols)
        thr_raw_all = _thr_bins_to_raw(feats_all, thr_all, mapper,
                                       int(n_bins))
        leaf_all = leaf_all.astype(np.float32) * np.float32(lr)
        for t in range(feats_all.shape[0]):
            booster.append_tree(feats_all[t], thr_raw_all[t], leaf_all[t],
                                gains_all[t], covers_all[t])
        prof.mark("materialize")

    if ckpt is not None and n_iter > 0:
        ckpt.save(resumed_iters + n_iter, {
            "booster.txt": booster.to_string(),
            "meta.json": {"completed_iterations": resumed_iters + n_iter},
        })
    prof.report(n_iter)
    if valid_sets and n_iter == 0:
        # fully-completed checkpointed run rerun idempotently: the eval loop
        # never executed, so keep the restored booster's best_iteration
        pass
    else:
        # ABSOLUTE iterations (warm-start init included): predict's
        # num_iteration cap slices the whole-model tree prefix
        booster.best_iteration = (init_trees // K_trees + best_iter
                                  if valid_sets
                                  else resumed_iters + n_iter)
    if patience and best_model is not None:
        # dart reaching the iteration budget without the patience branch
        # firing: later drop rounds rescaled the best iteration's trees in
        # place, so only the snapshot reproduces best_score — a truncation
        # of the final stack would not (unlike every other boosting mode)
        return best_model
    return booster
