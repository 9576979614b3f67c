"""Transfer-learning image featurization.

Parity: ``deep-learning/.../cntk/ImageFeaturizer.scala`` — wraps an inner
DNN, optionally cutting the head layers (``cutOutputLayers``,
``:100-108``): 0 = full model predictions (logits), 1 = headless features.
Auto-resizes images to the model's input shape and unrolls them into the
tensor feed (``:137-184``), dropping undecodable rows (``:176-180``).

TPU-first: the inner model is an :class:`~mmlspark_tpu.models.onnx_model.ONNXModel`
whose graph carries both ``logits`` and pre-head ``feat`` outputs, so cutting
layers is output selection on the same jitted XLA program — no graph surgery
per configuration.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.dataframe import DataFrame, object_col
from ..core.params import ComplexParam, HasInputCol, HasOutputCol, Param
from ..core.pipeline import Model
from ..image.schema import ImageSchema, decode_image
from ..image.unroll import _resize
from .onnx_model import ONNXModel

__all__ = ["ImageFeaturizer"]


class ImageFeaturizer(Model, HasInputCol, HasOutputCol):
    onnx_model = ComplexParam(default=None, doc="inner ONNXModel (or bytes)")
    cut_output_layers = Param(int, default=1,
                              doc="0 = logits, 1 = headless features "
                                  "(reference cutOutputLayers semantics)")
    input_size = Param(int, default=224, doc="model input H=W")
    channel_order = Param(str, default="rgb", choices=["rgb", "bgr"],
                          doc="channel order the model expects")
    scale = Param(float, default=1.0 / 255.0, doc="pixel scale factor")
    mean = Param((list, float), default=None, doc="per-channel mean (model order)")
    std = Param((list, float), default=None, doc="per-channel std (model order)")
    drop_na = Param(bool, default=True, doc="drop undecodable image rows")
    mini_batch_size = Param(int, default=64, doc="device batch size")
    feature_output = Param(str, default="feat", doc="graph output for features")
    logits_output = Param(str, default="logits", doc="graph output for logits")

    def __init__(self, onnx_model=None, **kw):
        super().__init__(**kw)
        self._set_default(input_col="image", output_col="features")
        if onnx_model is not None:
            self.set(onnx_model=onnx_model)

    def _inner(self) -> ONNXModel:
        m = self.get("onnx_model")
        if isinstance(m, (bytes, bytearray)):
            m = ONNXModel(bytes(m))
            self.set(onnx_model=m)
        if not isinstance(m, ONNXModel):
            raise TypeError("onnx_model must be an ONNXModel or ONNX bytes")
        return m

    def _prep_cell(self, cell) -> Optional[np.ndarray]:
        """image struct / bytes / array → HWC uint8.

        Host work stops at decode/resize/channel-order; the float scale,
        mean/std normalization, and HWC→CHW layout run ON DEVICE fused into
        the graph (the inner ONNXModel's transpose/normalize prep) — a
        uint8 image crosses the host→device link at 1/4 the bytes of the
        float32 tensor this method used to build, and the link is the
        bottleneck."""
        if cell is None:
            return None
        if isinstance(cell, (bytes, bytearray)):
            cell = decode_image(bytes(cell))
            if cell is None:
                return None
        if ImageSchema.is_image(cell):
            img = np.asarray(cell["data"], dtype=np.uint8)  # HWC BGR
        else:
            img = np.asarray(cell, dtype=np.uint8)
            if img.ndim == 2:
                img = img[:, :, None]
        size = self.get("input_size")
        if img.shape[0] != size or img.shape[1] != size:
            img = _resize(img, size, size)
        if img.shape[-1] == 1:
            img = np.repeat(img, 3, axis=-1)
        if self.get("channel_order") == "rgb" and img.shape[-1] >= 3:
            img = img[:, :, [2, 1, 0] + list(range(3, img.shape[-1]))]
        return np.ascontiguousarray(img)

    def _transform(self, df: DataFrame) -> DataFrame:
        inner = self._inner()
        tensors = [self._prep_cell(c) for c in df[self.get("input_col")]]
        keep = np.asarray([t is not None for t in tensors], dtype=bool)
        cur = df
        if self.get("drop_na"):
            cur = cur.filter(keep)
            tensors = [t for t in tensors if t is not None]
        elif not keep.all():
            raise ValueError("undecodable image rows present and drop_na=False")
        if not tensors:
            return cur.with_column(self.get("output_col"),
                                   object_col([]))
        tensor_col = "__img_tensor__"
        feed_name = list(inner.model_inputs())[0]
        out_name = (self.get("feature_output") if self.get("cut_output_layers") >= 1
                    else self.get("logits_output"))
        staged = cur.with_column(tensor_col, object_col(tensors))
        norm = {"scale": float(self.get("scale"))}
        if self.get_or_none("mean") is not None:
            norm["mean"] = [float(v) for v in np.atleast_1d(self.get("mean"))]
        if self.get_or_none("std") is not None:
            norm["std"] = [float(v) for v in np.atleast_1d(self.get("std"))]
        inner = inner.copy({"feed_dict": {feed_name: tensor_col},
                            "fetch_dict": {self.get("output_col"): out_name},
                            "mini_batch_size": self.get("mini_batch_size"),
                            # uint8 HWC over the link; layout + normalize
                            # fuse into the graph on device
                            "transpose_dict": {feed_name: [0, 3, 1, 2]},
                            "normalize_dict": {feed_name: norm}})
        out = inner.transform(staged)
        return out.drop(tensor_col)
