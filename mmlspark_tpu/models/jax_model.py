"""JaxModel — run any jittable callable as a pipeline stage.

The reference ships *two* deep-learning graph runners with one shape:
``ONNXModel`` and ``CNTKModel`` (``deep-learning/.../cntk/CNTKModel.scala:250-330``
— feed/fetch dict API, input coercion ``:387-434``, broadcast +
``mapPartitions`` evaluate). This framework deliberately subsumes the CNTK
path: legacy CNTK graphs convert to ONNX and run through :class:`ONNXModel`;
**new** models are native JAX functions — and this stage is their runner,
the generic non-ONNX model path.

Anything of the form ``apply(params, feeds) -> outputs`` is a model here:
a hand-written function, a flax/haiku ``Module.apply``, a zoo network. The
stage gives it the full DataFrame treatment the reference gives CNTK graphs:
minibatching, dtype management (bf16 on TPU), per-partition device pinning,
pipelined async dispatch, save/load (params as an npz pytree; the callable
by import path when it is a module-level function — the moral of
``CNTKFunctionParam``'s model-file reference).
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataframe import DataFrame
from ..core.params import ComplexParam, Param
from ..core.pipeline import Model
from ..ops.compile_cache import StageCounters, warm_up_model
from ..parallel.mesh import feed_placement
from .runner import BatchRunner, FrameOutputs, StagingSlabPool, collect

__all__ = ["JaxModel"]


class JaxModel(Model):
    """Run ``apply_fn(params, {feed: array}) -> {name: array} | array``
    over DataFrame columns in device minibatches."""

    apply_fn = ComplexParam(default=None,
                            doc="callable (params, feeds) -> outputs; "
                                "module-level functions survive save/load "
                                "by import path, closures are transient")
    model_params = ComplexParam(default=None,
                                doc="pytree of arrays passed as first arg")
    feed_dict = Param(dict, default={}, doc="{feed name: dataframe column}; "
                                            "empty = first column as 'input'")
    fetch_dict = Param(dict, default={}, doc="{output column: output name}; "
                                             "empty = every output under its "
                                             "own name")
    mini_batch_size = Param(int, default=64, doc="rows per device batch")
    compute_dtype = Param(str, default="float32",
                          doc="float feeds/params cast to this on device "
                              "(bfloat16 recommended on TPU)")
    pin_devices = Param(bool, default=True,
                        doc="round-robin partitions over local chips")
    mesh_sharded = Param(bool, default=False,
                         doc="SPMD inference over the default mesh's first "
                             "axis (batch sharded, params replicated); "
                             "overrides pin_devices — see ONNXModel")
    prefetch_depth = Param(int, default=2,
                           doc="prepared batches coerced/padded ahead on a "
                               "background worker while the current batch "
                               "dispatches; bounds host memory at that many "
                               "padded batches. 0 = prepare inline on the "
                               "dispatch thread")
    buckets = Param((list, int), default=[],
                    doc="custom padding-bucket ladder (sorted batch sizes); "
                        "empty = next-power-of-two. Warm-up and the runner "
                        "derive every padded shape through the same ladder")
    tuning = Param(str, default="", choices=["", "auto"],
                   doc="'auto' consults the measurement-driven tuning store "
                       "(MMLSPARK_TPU_TUNING_DIR): the fitted cost model "
                       "picks mini_batch_size, prefetch_depth and the "
                       "bucket ladder; a cold store keeps the defaults")

    def __init__(self, apply_fn: Optional[Callable] = None,
                 model_params=None, **kw):
        super().__init__(**kw)
        if apply_fn is not None:
            self.set(apply_fn=apply_fn)
        if model_params is not None:
            self.set(model_params=model_params)
        self._jitted = None
        self._device_params: Dict[Optional[int], object] = {}
        self._params_lock = threading.Lock()
        self._counters = StageCounters()
        self._staging = StagingSlabPool()
        self._tuning_decisions: Dict[tuple, object] = {}

    @property
    def stage_counters(self) -> StageCounters:
        """coerce/pad/h2d/compile/dispatch/d2h instrumentation, cumulative
        over every transform/warm_up on this instance."""
        return self._counters

    def set(self, **kwargs):
        # any reconfiguration invalidates the compiled program and the
        # cached device-resident params (mirrors ONNXModel's _jit_sig)
        out = super().set(**kwargs)
        if kwargs and hasattr(self, "_params_lock"):
            self._jitted = None
            # under the lock like ONNXModel.set: a _params_for_device call
            # racing the reset must see either the old cache or the empty
            # one, never a dict it is mid-populating
            with self._params_lock:
                self._device_params = {}
        if kwargs and getattr(self, "_tuning_decisions", None) is not None:
            self._tuning_decisions.clear()
        return out

    # -- tuning --------------------------------------------------------------
    def tuning_signature(self) -> str:
        """Stable identity for the observation store: the apply_fn's import
        path (the callable IS the model) plus the compute dtype."""
        fn = self.get_or_none("apply_fn")
        name = (f"{getattr(fn, '__module__', '?')}."
                f"{getattr(fn, '__qualname__', repr(fn))}" if fn is not None
                else "unset")
        return f"jax:{name}:{self.compute_dtype}"

    def _mesh_shape(self) -> str:
        """Topology stamp for tuning decisions: the default mesh's
        canonical shape string when this model dispatches mesh-sharded,
        else ``"single"`` — decisions learned on one chip layout never
        seed another (their cost surfaces differ by ICI collectives)."""
        from ..parallel.mesh import get_default_mesh, mesh_shape
        if not self.get("mesh_sharded"):
            return "single"
        return mesh_shape(get_default_mesh())

    def _resolve_tuning(self, histogram: Dict[int, int]):
        """The store's pick for this histogram (None = off or cold store);
        resolved sig-wide so warm-up and every partition share one ladder.
        Decisions are keyed (and the store filtered) by mesh shape too, so
        toggling ``mesh_sharded`` mid-life never reuses a stale ladder."""
        if self.get_or_none("tuning") != "auto":
            return None
        mesh = self._mesh_shape()
        key = (tuple(sorted(histogram.items())), mesh)
        if key not in self._tuning_decisions:
            from ..tuning.cost_model import resolve_tuning
            self._tuning_decisions[key] = resolve_tuning(
                self.tuning_signature(), "default", histogram,
                defaults=(self.mini_batch_size, self.prefetch_depth),
                mesh_shape=mesh)
        return self._tuning_decisions[key]

    def _runner_config(self, n_rows: int):
        ladder = tuple(self.buckets) if self.get_or_none("buckets") else None
        decision = self._resolve_tuning({int(n_rows): 1})
        if decision is None:
            return self.mini_batch_size, self.prefetch_depth, ladder
        return (decision.mini_batch_size, decision.prefetch_depth,
                decision.buckets)

    # -- jit ----------------------------------------------------------------
    def _ensure_jitted(self):
        if self._jitted is None:
            fn = self.apply_fn
            if fn is None:
                raise ValueError(
                    f"{self.uid}: apply_fn is unset (a closure param does "
                    f"not survive save/load; re-set it after loading)")
            compute_dt = jnp.dtype(self.compute_dtype)
            fetch = dict(self.fetch_dict)

            def run(params, feeds):
                feeds = {k: (v.astype(compute_dt)
                             if jnp.issubdtype(v.dtype, jnp.floating)
                             and v.dtype != compute_dt else v)
                         for k, v in feeds.items()}
                out = fn(params, feeds)
                if not isinstance(out, dict):
                    out = {"output": out}
                if fetch:
                    return {col: out[name] for col, name in fetch.items()}
                return out

            self._jitted = jax.jit(run)
        return self._jitted

    def _cast_tree(self, params):
        """Float leaves → compute_dtype, on whatever devices hold them."""
        if self.compute_dtype == "float32" or params is None:
            return params
        dt = jnp.dtype(self.compute_dtype)
        cast = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda v: (v.astype(dt)
                       if jnp.issubdtype(v.dtype, jnp.floating)
                       else v), p))
        return cast(params)

    def _params_for_device(self, device):
        key = id(device) if device is not None else None
        with self._params_lock:
            if key not in self._device_params:
                params = self.get_or_none("model_params")
                # f32 over the wire, compute_dtype cast on device (narrow
                # host buffers hit a slow transfer path; see ONNXModel).
                # staging stays under the lock on purpose: first touch per
                # device must be single-flight — two racing threads would
                # both device_put the full param tree (duplicate HBM +
                # link traffic); steady state is a dict hit
                self._device_params[key] = self._cast_tree(
                    jax.device_put(params, device)  # tpulint: disable=TPU014
                    if device is not None
                    else jax.device_put(params))    # tpulint: disable=TPU014
            return self._device_params[key]

    def _params_for_mesh(self, mesh):
        from ..parallel.mesh import replicated_sharding
        key = ("mesh", mesh)
        with self._params_lock:
            if key not in self._device_params:
                # single-flight staging, as in _params_for_device
                self._device_params[key] = self._cast_tree(jax.device_put(  # tpulint: disable=TPU014
                    self.get_or_none("model_params"),
                    replicated_sharding(mesh)))
            return self._device_params[key]

    # -- execution ----------------------------------------------------------
    @staticmethod
    def _coerce_col(col: np.ndarray) -> np.ndarray:
        if col.dtype == object:
            col = np.stack([np.asarray(v) for v in col])
        arr = np.asarray(col)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        return arr

    def _placement_params(self, pidx: int):
        placement = feed_placement(
            self.get("mesh_sharded"), pidx, self.pin_devices)
        params = (self._params_for_mesh(placement.mesh)
                  if placement.mesh is not None
                  else self._params_for_device(placement.device))
        return placement, params

    def _run_batches(self, part: DataFrame, pidx: int,
                     outputs: FrameOutputs) -> DataFrame:
        """One partition through the shared feed/drain pipeline (see
        :class:`~mmlspark_tpu.models.runner.BatchRunner` — prefetch, async
        h2d, overlapped d2h drain; the same machinery as ONNXModel), its
        rows written to their place in the frame's ``outputs``."""
        jitted = self._ensure_jitted()
        feed = dict(self.feed_dict) or {"input": part.columns[0]}
        placement, params = self._placement_params(pidx)

        # resident input columns feed device slices (no host coercion,
        # zero h2d payload; BatchRunner counts the residency hits)
        resident = {col_name: part.device_column(col_name).device_array()
                    for col_name in feed.values()
                    if part.is_resident(col_name)}

        def coerce(sl: slice) -> Dict[str, np.ndarray]:
            out = {}
            for feed_name, col_name in feed.items():
                dev = resident.get(col_name)
                out[feed_name] = dev[sl] if dev is not None \
                    else self._coerce_col(part[col_name][sl])
            return out

        mbs, depth, ladder = self._runner_config(len(part))
        runner = BatchRunner(jitted, params, coerce, placement.put,
                             shards=placement.shards,
                             mini_batch_size=mbs,
                             prefetch_depth=depth,
                             counters=self._counters,
                             staging=self._staging,
                             buckets=ladder,
                             model_sig=self.tuning_signature(),
                             placement_key=str(placement.key))
        return part.with_columns(
            collect(runner.drain_each(runner.run(len(part))), outputs, pidx))

    # -- AOT warm-up ---------------------------------------------------------
    def warm_up(self, input_specs: Dict[str, tuple],
                batch_sizes: Optional[List[int]] = None,
                background: bool = False):
        """Compile every padding-bucket shape ahead of first traffic.

        ``apply_fn`` is opaque (no graph metadata to introspect), so
        ``input_specs`` is required: {feed name: (dtype, per-row shape)}.
        Otherwise identical to :meth:`ONNXModel.warm_up` — one zero batch
        per bucket per placement, populating the jit cache (and the
        persistent compilation cache when enabled).
        """
        jitted = self._ensure_jitted()
        specs = {name: (np.dtype(dt), tuple(shape))
                 for name, (dt, shape) in input_specs.items()}
        sizes = [int(b) for b in (batch_sizes or [self.mini_batch_size])]
        ladder = tuple(self.buckets) if self.get_or_none("buckets") else None
        decision = self._resolve_tuning({s: 1 for s in sizes})
        if decision is not None:
            sizes = list(decision.warm_up_sizes) or sizes
            ladder = decision.buckets
        return warm_up_model(self, jitted, specs, sizes,
                             background=background, buckets=ladder)

    def _transform(self, df: DataFrame) -> DataFrame:
        self._ensure_jitted()
        return df.map_partitions(functools.partial(
            self._run_batches, outputs=FrameOutputs(df.partition_bounds())))

    # -- persistence --------------------------------------------------------
    def _load_extra(self, path: str) -> None:
        self._jitted = None
        # load-time rebuild of a just-deserialized instance: the lock
        # itself is recreated on the next line, so nothing can hold it
        # tpulint: disable=TPU012
        self._device_params = {}
        self._params_lock = threading.Lock()
        self._counters = StageCounters()
        self._staging = StagingSlabPool()
        self._tuning_decisions = {}
