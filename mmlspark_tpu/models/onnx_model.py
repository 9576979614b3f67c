"""ONNXModel — batched ONNX inference on TPU through the DataFrame API.

Parity surface: the reference's ``ONNXModel``
(``deep-learning/.../onnx/ONNXModel.scala``):

* ``feed_dict`` {model input → column} / ``fetch_dict`` {column → model
  output} (`SharedParams.scala:9-33`)
* ``softmax_dict`` / ``argmax_dict`` post-ops (`ONNXModel.scala:519-562`)
* minibatch → coerce → run per partition → flatten (`ONNXModel.scala:482-517`)
* device selection per partition (`ONNXModel.scala:293-303`) → here chips
  round-robin via ``parallel.device_for_partition``.

TPU-first differences: the graph is compiled by XLA (no ORT session); batches
are padded to power-of-two buckets so the jit cache stays small
(`ops/padding.py`); model I/O metadata comes from the proto directly
(`ONNXModel.scala:437-457` needs a live ORT session for this).
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataframe import DataFrame
from ..core.params import ComplexParam, Param
from ..core.pipeline import Model
from ..onnx.convert import ConvertedModel, convert_model
from ..ops.compile_cache import (StageCounters, resolve_input_specs,
                                 warm_up_model)
from ..core.residency import DeviceColumn
from ..observability import tracing as _tracing
from ..parallel.mesh import feed_placement, local_devices
from .runner import BatchRunner, FrameOutputs, StagingSlabPool, collect

__all__ = ["ONNXModel"]


class ONNXModel(Model):
    model_bytes = ComplexParam(doc="serialized ONNX ModelProto")
    feed_dict = Param(dict, default={}, doc="{model input name: dataframe column}")
    fetch_dict = Param(dict, default={}, doc="{output column: model output name}")
    mini_batch_size = Param(int, default=64, doc="rows per device batch")
    softmax_dict = Param(dict, default={}, doc="{output col: col to softmax}")
    argmax_dict = Param(dict, default={}, doc="{output col: col to argmax}")
    compute_dtype = Param(str, default="float32",
                          doc="cast float inputs/params to this dtype "
                              "(bfloat16 recommended on TPU)")
    normalize_dict = Param(dict, default={},
                           doc="{model input: {scale, mean, std}} applied on "
                               "device after the dtype cast — the tensor "
                               "normalization the reference does host-side in "
                               "ImageTransformer (ImageTransformer.scala:417+) "
                               "fused into the XLA graph; mean/std broadcast "
                               "over the channel axis (axis 1)")
    transpose_dict = Param(dict, default={},
                           doc="{model input: permutation} applied on device "
                               "before normalization, e.g. NHWC uint8 images "
                               "to the NCHW the graph expects: [0, 3, 1, 2]")
    pin_devices = Param(bool, default=True,
                        doc="round-robin partitions over local chips")
    mesh_sharded = Param(bool, default=False,
                         doc="SPMD inference: shard each batch's leading "
                             "axis over the default mesh's first axis "
                             "(params replicated) — one XLA program spans "
                             "every chip instead of one partition per chip. "
                             "Install a mesh with MeshContext/"
                             "set_default_mesh; overrides pin_devices")
    external_data_dir = Param(str, default="",
                              doc="directory with sidecar files for models "
                                  "saved with external data")
    weights_override = ComplexParam(default=None,
                                    doc="npz payload of fine-tuned params "
                                        "layered over the graph's own "
                                        "initializers (ONNXEstimator.fit "
                                        "sets this; the original model "
                                        "bytes stay untouched)")
    quantize = Param(str, default="", choices=["", "int8"],
                     doc="weight-only quantization: 2-D float weights live "
                         "in HBM as symmetric per-column int8 + scale and "
                         "dequantize on device (XLA fuses the multiply "
                         "into the consumer matmul) — 4x less weight "
                         "bandwidth, activations stay in compute_dtype")
    prefetch_depth = Param(int, default=2,
                           doc="prepared batches coerced/padded ahead on a "
                               "background worker while the current batch "
                               "dispatches; bounds host memory at that many "
                               "padded batches. 0 = prepare inline on the "
                               "dispatch thread")
    output_device = Param(bool, default=False,
                          doc="keep fetch outputs device-resident (attached "
                              "as DeviceColumns, no drain) so a downstream "
                              "device stage or sink pays the single d2h; "
                              "outputs keep their device dtypes (bf16 stays "
                              "bf16, argmax stays int32) until "
                              "DataFrame.to_host materializes them")
    buckets = Param((list, int), default=[],
                    doc="custom padding-bucket ladder (sorted batch sizes); "
                        "empty = next-power-of-two. Warm-up and the runner "
                        "derive every padded shape through the same ladder, "
                        "so only these buckets ever compile")
    tuning = Param(str, default="", choices=["", "auto"],
                   doc="'auto' consults the measurement-driven tuning store "
                       "(MMLSPARK_TPU_TUNING_DIR) at transform/warm_up: the "
                       "fitted cost model picks mini_batch_size, "
                       "prefetch_depth and the bucket ladder for the "
                       "observed row counts; a cold store keeps the "
                       "defaults and this run's measurements train it")

    def __init__(self, model_bytes: Optional[bytes] = None, **kw):
        super().__init__(**kw)
        if model_bytes is not None:
            self.set(model_bytes=model_bytes)
        self._converted: Optional[ConvertedModel] = None
        self._jitted = None
        self._jit_sig = None
        self._fused_cols: set = set()
        self._argmax_cols: set = set()
        self._out_col_names: List[str] = []
        self._device_params: Dict[Optional[int], dict] = {}
        self._params_lock = threading.Lock()
        self._counters = StageCounters()
        self._staging = StagingSlabPool()
        self._tuning_sig: Optional[str] = None
        self._tuning_decisions: Dict[tuple, object] = {}

    @property
    def stage_counters(self) -> StageCounters:
        """coerce/pad/h2d/compile/dispatch/d2h instrumentation, cumulative
        over every transform/warm_up on this instance."""
        return self._counters

    # -- tuning --------------------------------------------------------------
    def tuning_signature(self) -> str:
        """Stable identity for the observation store: content hash of the
        graph plus the knobs that change its cost profile."""
        sig = getattr(self, "_tuning_sig", None)
        if sig is None:
            from ..onnx.proto import model_content_digest
            mb = self.get_or_none("model_bytes") or b""
            h = model_content_digest(bytes(mb))[:16]
            sig = f"onnx:{h}:{self.compute_dtype}:{self.quantize or 'fp'}"
            self._tuning_sig = sig
        return sig

    def _resolve_tuning(self, histogram: Dict[int, int]):
        """The store's pick for this histogram (None = off or cold store).
        Resolved sig-wide (placement "default"): one vocabulary serves all
        chips, so warm-up and every partition agree on the ladder."""
        if self.get_or_none("tuning") != "auto":
            return None
        key = tuple(sorted(histogram.items()))
        if key not in self._tuning_decisions:
            from ..tuning.cost_model import resolve_tuning
            self._tuning_decisions[key] = resolve_tuning(
                self.tuning_signature(), "default", histogram,
                defaults=(self.mini_batch_size, self.prefetch_depth))
        return self._tuning_decisions[key]

    def _runner_config(self, n_rows: int):
        """Effective ``(mini_batch_size, prefetch_depth, ladder)`` — the
        Params unless ``tuning="auto"`` found a measured pick."""
        ladder = tuple(self.buckets) if self.get_or_none("buckets") else None
        decision = self._resolve_tuning({int(n_rows): 1})
        if decision is None:
            return self.mini_batch_size, self.prefetch_depth, ladder
        return (decision.mini_batch_size, decision.prefetch_depth,
                decision.buckets)

    # -- metadata (proto-only, no session) ----------------------------------
    def _ensure_converted(self) -> ConvertedModel:
        if self._converted is None:
            self._converted = convert_model(
                self.get("model_bytes"),
                external_data_dir=self.external_data_dir or None)
        return self._converted

    def _fetch_map(self, cm: ConvertedModel) -> Dict[str, str]:
        return dict(self.fetch_dict) or {n: n for n in cm.output_names}

    def _ensure_jitted(self):
        """One jitted program: model graph + softmax/argmax post-ops fused.

        The reference applies softmax/argmax as per-row UDFs *after* the
        inference pass (``ONNXModel.scala:519-562``); on TPU those are free
        when fused into the XLA graph, so outputs cross the host boundary
        exactly once.
        """
        cm = self._ensure_converted()
        fetch = self._fetch_map(cm)
        softmax = {k: v for k, v in self.softmax_dict.items() if v in fetch}
        argmax = {k: v for k, v in self.argmax_dict.items() if v in fetch}
        normalize = dict(self.normalize_dict)
        transpose = dict(self.transpose_dict)
        float_inputs = {vi.name for vi in cm.inputs
                        if np.issubdtype(vi.numpy_dtype, np.floating)}
        bad_norm = set(normalize) - float_inputs
        if bad_norm:
            # normalizing an integer-typed model input would silently zero it
            # (e.g. uint8 * 1/255 truncates); the uint8-image case is a float
            # model input fed an int column, which is fine
            raise ValueError(
                f"normalize_dict targets non-float model inputs {sorted(bad_norm)}; "
                f"normalization requires a float-typed graph input")
        compute_dt = jnp.dtype(self.compute_dtype)
        sig = (tuple(sorted(fetch.items())), tuple(sorted(softmax.items())),
               tuple(sorted(argmax.items())),
               tuple(sorted((k, str(v)) for k, v in normalize.items())),
               tuple(sorted((k, tuple(v)) for k, v in transpose.items())),
               str(compute_dt), self.quantize)
        if self._jitted is None or self._jit_sig != sig:
            if set(fetch.values()) != set(cm.output_names):
                # dead-node elimination from the requested outputs: a
                # training graph (loss output + labels input) serves
                # inference on just its prediction outputs with the loss
                # subtree pruned away (no dummy label feeds at serving
                # time), and fetching an internal tensor name works too —
                # the cut-layer read ImageFeaturizer's reference does by
                # re-exporting a truncated model. Inside the jit-miss
                # branch: the ancestor walk is trace-time work, not
                # per-partition overhead.
                cm = cm.pruned(sorted(set(fetch.values())))
            def prep(name, x):
                """On-device input prep: layout, dtype cast, normalization.

                Feeds cross the host→device link in the column's native dtype
                (uint8 images are 4x smaller than float32, and a host-side
                bfloat16 cast would both burn CPU and hit the slow narrow-type
                transfer path); all massaging happens on device where it is
                fused into the first convolution's input.
                """
                perm = transpose.get(name)
                if perm is not None:
                    x = jnp.transpose(x, perm)
                if name in float_inputs and x.dtype != compute_dt:
                    x = x.astype(compute_dt)
                spec = normalize.get(name)
                if spec:
                    scale = spec.get("scale")
                    if scale is not None:
                        x = x * jnp.asarray(scale, x.dtype)
                    mean = spec.get("mean")
                    if mean is not None:
                        m = jnp.asarray(mean, x.dtype)
                        x = x - m.reshape((1, -1) + (1,) * (x.ndim - 2))
                    std = spec.get("std")
                    if std is not None:
                        s = jnp.asarray(std, x.dtype)
                        x = x / s.reshape((1, -1) + (1,) * (x.ndim - 2))
                return x

            def run(params, feeds):
                feeds = {k: prep(k, v) for k, v in feeds.items()}
                params = self._unpack_params(params, compute_dt)
                outs = cm(params, feeds)
                cols = {col: outs[name] for col, name in fetch.items()}
                for out_col, src in softmax.items():
                    cols[out_col] = jax.nn.softmax(
                        cols[src].astype(jnp.float32), axis=-1)
                for out_col, src in argmax.items():
                    cols[out_col] = jnp.argmax(cols[src], axis=-1).astype(jnp.int32)
                return cols

            self._jitted = jax.jit(run)
            self._jit_sig = sig
            self._fused_cols = set(softmax) | set(argmax)
            self._argmax_cols = set(argmax)
            self._out_col_names = list(fetch) + \
                [c for c in self._fused_cols if c not in fetch]
        return self._jitted

    def model_inputs(self) -> Dict[str, tuple]:
        cm = self._ensure_converted()
        return {vi.name: (vi.numpy_dtype, tuple(vi.shape)) for vi in cm.inputs}

    def model_outputs(self) -> Dict[str, tuple]:
        cm = self._ensure_converted()
        return {vi.name: (vi.numpy_dtype, tuple(vi.shape)) for vi in cm.outputs}

    # -- column coercion (parity: ONNXModel.coerceBatchedDf :564-584) -------
    def _coerce(self, col: np.ndarray, dtype, shape,
                device_prepped: bool = False) -> np.ndarray:
        if col.dtype == object:
            col = np.stack([np.asarray(v) for v in col])
        arr = np.asarray(col)
        want = np.dtype(dtype)
        if want.kind == "f":
            # floats cross the wire as-is (except f64, halved to f32: the
            # model can't use the precision and transfer is the bottleneck);
            # the cast to compute_dtype happens on device in the jitted prep
            if arr.dtype == np.float64:
                arr = arr.astype(np.float32)
            elif arr.dtype.kind not in "fiu":
                arr = arr.astype(np.float32)
        elif arr.dtype != want:
            arr = arr.astype(want)
        if device_prepped:
            return arr  # layout handled on device; shape is not NCHW yet
        # reshape flat rows to the model's per-row shape if one is declared
        row_shape = [d for d in shape[1:] if isinstance(d, int)]
        if row_shape and list(arr.shape[1:]) != row_shape \
                and int(np.prod(arr.shape[1:])) == int(np.prod(row_shape)):
            arr = arr.reshape((arr.shape[0],) + tuple(row_shape))
        return arr

    def _coerce_device(self, arr, dtype, shape,
                       device_prepped: bool = False):
        """:meth:`_coerce` for an already-resident (device) column slice —
        same dtype/shape policy, but every op is a device op so the column
        never round-trips through host."""
        want = np.dtype(dtype)
        if want.kind == "f":
            if arr.dtype == jnp.float64:
                arr = arr.astype(jnp.float32)
        elif arr.dtype != want:
            arr = arr.astype(want)
        if device_prepped:
            return arr
        row_shape = [d for d in shape[1:] if isinstance(d, int)]
        if row_shape and list(arr.shape[1:]) != row_shape \
                and int(np.prod(arr.shape[1:])) == int(np.prod(row_shape)):
            arr = arr.reshape((arr.shape[0],) + tuple(row_shape))
        return arr

    def _cast_params(self, params: dict) -> dict:
        """Float params → compute_dtype, on whatever devices hold them."""
        if self.compute_dtype == "float32":
            return params
        dt = jnp.dtype(self.compute_dtype)
        cast = jax.jit(
            lambda p: {k: (v.astype(dt)
                           if jnp.issubdtype(v.dtype, jnp.floating)
                           else v) for k, v in p.items()})
        return cast(params)

    # -- int8 weight-only quantization --------------------------------------
    _QUANT_MIN_DIM = 16

    def _quantizable(self, v) -> bool:
        """2-D float weights (the matmul bulk of transformer/MLP graphs);
        conv kernels (4-D) and vectors stay full precision."""
        return (v.ndim == 2 and jnp.issubdtype(v.dtype, jnp.floating)
                and min(v.shape) >= self._QUANT_MIN_DIM)

    def _pack_params(self, params: dict) -> dict:
        """Symmetric per-column int8 packing: HBM holds q (int8) + a
        per-column scale; the jitted run dequantizes on device, where XLA
        fuses the multiply into the consumer matmul — weight reads cost
        1/4 the bandwidth (weight-ONLY quantization: activations and
        accumulation stay in compute_dtype)."""
        @jax.jit
        def pack(p):
            out = {}
            for k, v in p.items():
                if self._quantizable(v):
                    v32 = v.astype(jnp.float32)
                    s = jnp.max(jnp.abs(v32), axis=0, keepdims=True) / 127.0
                    s = jnp.where(s == 0, jnp.float32(1.0), s)
                    q = jnp.clip(jnp.round(v32 / s), -127, 127) \
                        .astype(jnp.int8)
                    out[k] = {"q": q, "s": s}
                else:
                    out[k] = v
            return out
        return pack(params)

    @staticmethod
    def _unpack_params(params: dict, dt) -> dict:
        return {k: ((v["q"].astype(dt) * v["s"].astype(dt))
                    if isinstance(v, dict) else v)
                for k, v in params.items()}

    def _effective_params(self, cm: ConvertedModel) -> dict:
        """Graph initializers with any fine-tuned override layered on top
        (``weights_override`` npz — set by ONNXEstimator.fit)."""
        ov = self.get_or_none("weights_override")
        if not ov:
            return cm.params
        import io
        with np.load(io.BytesIO(ov)) as z:
            override = {k: z[k] for k in z.files}
        unknown = sorted(set(override) - set(cm.params))
        if unknown:
            raise ValueError(
                f"weights_override names unknown params {unknown[:5]} "
                "(the override must come from this graph's fine-tune)")
        return {**cm.params, **override}

    _PARAM_CACHE_KEYS = ("weights_override", "quantize", "compute_dtype")

    def set(self, **kwargs):
        if any(k in kwargs for k in self._PARAM_CACHE_KEYS) \
                and getattr(self, "_device_params", None):
            # cached device params embed the previous override/packing/dtype
            # cast — drop them so the change takes effect (an id()-keyed
            # cache would risk stale hits after the old payload's address is
            # reused; a compute_dtype change used to leave bf16-cast params
            # serving a float32 run). getattr: Params.__init__ may route
            # constructor kwargs through set() before __init__ has built
            # the caches.
            with self._params_lock:
                self._device_params.clear()
        if kwargs and getattr(self, "_tuning_decisions", None) is not None:
            # any reconfiguration may change the model signature or the
            # defaults the tuner compares against
            self._tuning_decisions.clear()
            self._tuning_sig = None
        return super().set(**kwargs)

    def _params_for_device(self, device) -> dict:
        if device is None:
            # normalize to the concrete default device so pinned and
            # unpinned callers share one cached weight copy
            device = local_devices()[0]
        key = id(device)
        with self._params_lock:
            if key not in self._device_params:
                cm = self._ensure_converted()
                # transfer in f32, cast on device: narrow-dtype host buffers
                # (bfloat16) take a slow serialization path over the link
                # params are committed to `device`; the cast jit follows
                # its operands. staging stays under the lock on purpose:
                # first touch per device must be single-flight — racing
                # threads would both device_put the full param tree
                p = self._cast_params(
                    jax.device_put(self._effective_params(cm), device))  # tpulint: disable=TPU014
                if self.quantize == "int8":
                    p = self._pack_params(p)
                self._device_params[key] = p
            return self._device_params[key]

    def _params_for_mesh(self, mesh) -> dict:
        """Weights replicated over the mesh (cached per mesh)."""
        from ..parallel.mesh import replicated_sharding
        key = ("mesh", mesh)
        with self._params_lock:
            if key not in self._device_params:
                cm = self._ensure_converted()
                # single-flight staging, as in _params_for_device
                p = self._cast_params(
                    jax.device_put(self._effective_params(cm),  # tpulint: disable=TPU014
                                   replicated_sharding(mesh)))
                if self.quantize == "int8":
                    p = self._pack_params(p)
                self._device_params[key] = p
            return self._device_params[key]

    # -- execution ----------------------------------------------------------
    def _placement_params(self, pidx: int):
        placement = feed_placement(
            self.get("mesh_sharded"), pidx, self.pin_devices)
        params = (self._params_for_mesh(placement.mesh)
                  if placement.mesh is not None
                  else self._params_for_device(placement.device))
        return placement, params

    def _run_batches(self, part: DataFrame, pidx: int,
                     outputs: FrameOutputs) -> DataFrame:
        """One partition through the shared feed/drain pipeline, its rows
        written to their place in the frame's ``outputs``.

        :class:`BatchRunner` overlaps all three host boundaries: coerce/pad
        of batch k+1 on a prefetch worker, async host→device puts at
        dispatch, ``copy_to_host_async`` per batch, each batch written to
        its place as its fetch lands (the reference's per-batch
        ``session.run`` + NIO-buffer marshalling, ``ONNXModel.scala:305-402``,
        is fully synchronous — this pipelining is the TPU-side throughput
        win).
        """
        cm = self._ensure_converted()
        jitted = self._ensure_jitted()
        feed = self.feed_dict or {cm.input_names[0]: part.columns[0]}
        in_meta = {vi.name: vi for vi in cm.inputs}
        placement, params = self._placement_params(pidx)

        # resident input columns feed device slices straight through —
        # no host coercion, no padding slab, zero h2d payload (BatchRunner
        # counts the residency hits); one concat per partition, then every
        # batch slice is a cheap device view
        resident = {col_name: part.device_column(col_name).device_array()
                    for col_name in feed.values()
                    if part.is_resident(col_name)}

        def coerce(sl: slice) -> Dict[str, np.ndarray]:
            out = {}
            for input_name, col_name in feed.items():
                meta = in_meta[input_name]
                prepped = input_name in self.transpose_dict
                dev = resident.get(col_name)
                if dev is not None:
                    out[input_name] = self._coerce_device(
                        dev[sl], meta.numpy_dtype, meta.shape,
                        device_prepped=prepped)
                else:
                    out[input_name] = self._coerce(
                        part[col_name][sl], meta.numpy_dtype, meta.shape,
                        device_prepped=prepped)
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
        pending = runner.run(len(part))
        if self.output_device:
            # keep outputs resident: no drain — the sink (DataFrame.to_host
            # or a downstream device stage) decides when to cross back
            out = part
            for col_name in self._out_col_names:
                chunks = [outs[col_name][:b] for outs, b in pending if b]
                if not chunks:
                    chunks = [jnp.zeros((0,), dtype=jnp.float32)]
                out = out.with_device_column(
                    col_name, DeviceColumn.from_device(chunks))
            return out

        # each batch as its fetch lands: the padding cut, bf16 widened, to
        # the rows' place in the frame's arrays, under the device's work on
        # the batches behind it
        with _tracing.span("onnx.collect", batches=len(pending)):
            cols = collect(runner.drain_each(pending), outputs, pidx,
                           self._out_col_names)
        return part.with_columns(
            cols or {col_name: np.zeros((0,), dtype=np.float32)
                     for col_name in self._out_col_names})

    # -- AOT warm-up ---------------------------------------------------------
    def warm_up(self, batch_sizes: Optional[List[int]] = None,
                input_specs: Optional[Dict[str, tuple]] = None,
                background: bool = False):
        """Compile every padding-bucket shape ahead of first traffic.

        Runs one zero-filled batch per bucket through the jitted program on
        every placement real traffic can hit (each pinned chip, or the
        default mesh), so neither bench nor serving eats a compile stall
        mid-stream — and, with the persistent compilation cache enabled
        (``JAX_COMPILATION_CACHE_DIR``), neither does the *next*
        process.

        ``batch_sizes`` defaults to ``[mini_batch_size]``; pass the expected
        ragged sizes too to pre-warm their buckets. ``input_specs`` maps a
        model input to its fed ``(dtype, per-row shape)`` and is required
        when a column feeds a different dtype/layout than the graph declares
        (e.g. uint8 HWC images into a float NCHW input via
        ``transpose_dict``) or when the declared shape is symbolic.
        ``background=True`` warms on a daemon thread and returns it;
        otherwise returns ``{"buckets", "compiles", "seconds",
        "placements"}``.
        """
        cm = self._ensure_converted()
        jitted = self._ensure_jitted()
        fed = dict(self.feed_dict) or {cm.input_names[0]: None}
        specs = resolve_input_specs(cm.inputs, fed, self.transpose_dict,
                                    overrides=input_specs)
        sizes = [int(b) for b in (batch_sizes or [self.mini_batch_size])]
        ladder = tuple(self.buckets) if self.get_or_none("buckets") else None
        decision = self._resolve_tuning({s: 1 for s in sizes})
        if decision is not None:
            # compile exactly the chosen vocabulary, not the full
            # power-of-two ladder
            sizes = list(decision.warm_up_sizes) or sizes
            ladder = decision.buckets
        return warm_up_model(self, jitted, specs, sizes,
                             background=background, buckets=ladder)

    def _transform(self, df: DataFrame) -> DataFrame:
        self._ensure_converted()
        self._ensure_jitted()
        outputs = FrameOutputs(
            df.partition_bounds(),
            {col_name: np.int64 for col_name in self._argmax_cols})
        out = df.map_partitions(
            functools.partial(self._run_batches, outputs=outputs))
        # host fallback for post-ops whose source column does not come out of
        # the jitted graph (parity: softMaxTransform/argMaxTransform :519-562)
        for out_col, src_col in self.softmax_dict.items():
            if out_col in self._fused_cols:
                continue
            out = out.with_column(out_col, _host_softmax(out[src_col]))
        for out_col, src_col in self.argmax_dict.items():
            if out_col in self._fused_cols:
                continue
            out = out.with_column(out_col, _host_argmax(out[src_col]))
        return out

    # -- persistence: rebuild session state after load ----------------------
    def _load_extra(self, path: str) -> None:
        self._converted = None
        self._jitted = None
        self._jit_sig = None
        self._fused_cols = set()
        self._argmax_cols = set()
        self._out_col_names = []
        # load-time rebuild of a just-deserialized instance: the lock
        # itself is recreated on the next line, so nothing can hold it
        # tpulint: disable=TPU012
        self._device_params = {}
        self._params_lock = threading.Lock()
        self._counters = StageCounters()
        self._staging = StagingSlabPool()
        self._tuning_sig = None
        self._tuning_decisions = {}


def _host_softmax(col: np.ndarray) -> np.ndarray:
    if col.dtype != object:
        v = np.asarray(col, dtype=np.float64)
        e = np.exp(v - v.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)
    probs = np.empty(len(col), dtype=object)
    for i, v in enumerate(col):
        v = np.asarray(v, dtype=np.float64)
        e = np.exp(v - v.max(axis=-1, keepdims=True))
        probs[i] = e / e.sum(axis=-1, keepdims=True)
    return probs


def _host_argmax(col: np.ndarray) -> np.ndarray:
    if col.dtype != object:
        return np.argmax(np.asarray(col), axis=-1).astype(np.int64)
    return np.asarray([int(np.argmax(np.asarray(v))) for v in col],
                      dtype=np.int64)
