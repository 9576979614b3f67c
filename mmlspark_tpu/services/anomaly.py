"""Anomaly-detection service transformers.

Parity: ``cognitive/.../AnomalyDetection.scala`` (249 LoC):
``DetectLastAnomaly`` / ``DetectEntireSeries(DetectAnomalies)`` POST a
``{"series": [{timestamp, value}], "granularity": ...}`` payload;
``SimpleDetectAnomalies`` groups rows by key and attaches per-row results.

Because a TPU cluster has no Azure dependency, ``SimpleDetectAnomalies``
can also run fully local (``local_fallback=True``): a jitted
median/MAD z-score detector — same output shape, no service required.
"""

from __future__ import annotations

import numpy as np

from ..core.dataframe import DataFrame, object_col
from .base import ServiceParam, ServiceTransformer
from ..core.params import Param

__all__ = ["AnomalyBase", "DetectLastAnomaly", "DetectAnomalies",
           "SimpleDetectAnomalies"]


def _group_indices(groups) -> "dict":
    """One-pass {group: np.ndarray(row indices)}, insertion-ordered."""
    bucket: dict = {}
    for i, g in enumerate(groups):
        bucket.setdefault(g, []).append(i)
    return {g: np.asarray(ix) for g, ix in bucket.items()}


class AnomalyBase(ServiceTransformer):
    series = ServiceParam(list, is_required=True,
                          doc="list of {timestamp, value} points")
    granularity = ServiceParam(str, default="daily", doc="series granularity")
    max_anomaly_ratio = ServiceParam(float, payload_name="maxAnomalyRatio",
                                     doc="expected max anomaly fraction")
    sensitivity = ServiceParam(int, doc="detector sensitivity 0-99")

    def _payload(self, row: dict):
        p = {"series": self.get_value_opt(row, "series"),
             "granularity": self.get_value_opt(row, "granularity")}
        for extra in ("max_anomaly_ratio", "sensitivity"):
            v = self.get_value_opt(row, extra)
            if v is not None:
                sp = self.params()[extra]
                p[sp.payload_name or extra] = v
        return p


class DetectLastAnomaly(AnomalyBase):
    """Parity: ``DetectLastAnomaly`` — /last endpoint semantics."""


class DetectAnomalies(AnomalyBase):
    """Parity: ``DetectEntireSeries`` — whole-series batch detection."""


class SimpleDetectAnomalies(AnomalyBase):
    """Grouped per-key detection (parity: ``SimpleDetectAnomalies``), with an
    optional local jitted MAD z-score detector when no service URL is set."""

    group_col = Param(str, default="group", doc="series grouping column")
    timestamp_col = Param(str, default="timestamp", doc="timestamp column")
    value_col = Param(str, default="value", doc="value column")
    local_threshold = Param(float, default=3.5, doc="local MAD z threshold")

    def _transform(self, df: DataFrame) -> DataFrame:
        if self.get_or_none("url") is not None:
            return self._service_transform(df)
        return self._local_transform(df)

    def _service_transform(self, df: DataFrame) -> DataFrame:
        # grouped mode aggregates rows per key, so column-bound service
        # params (other than the synthesized series) cannot be resolved
        for n, p in self._service_params().items():
            tagged = self.get_or_none(n)
            if n != "series" and tagged is not None and tagged["kind"] == "col":
                raise ValueError(
                    f"SimpleDetectAnomalies: service param {n!r} is bound to a "
                    "column; grouped mode only supports scalar params")
        group_rows = _group_indices(df[self.get("group_col")])
        ts = df[self.get("timestamp_col")]
        vals = df[self.get("value_col")]
        series_col = []
        for idxs in group_rows.values():
            series_col.append([{"timestamp": str(ts[i]), "value": float(vals[i])}
                               for i in idxs])
        # ONE batched probe transform: every group's request goes through the
        # same client at the transformer's concurrency
        probe = DetectAnomalies(url=self.get("url"),
                                concurrency=self.get("concurrency"),
                                timeout=self.get("timeout"),
                                key_header=self.get("key_header"),
                                method=self.get("method"),
                                output_col="__out__", error_col="__err__")
        for n in self._service_params():   # scalar service params (key, …)
            if n != "series" and self.get_or_none(n) is not None:
                probe.set(**{n: self.get(n)})
        probe.set_vector_param("series", "__series__")
        res = probe.transform(DataFrame({"__series__": object_col(series_col)}))

        out = np.empty(len(df), dtype=object)
        errs = np.empty(len(df), dtype=object)
        for g_i, idxs in enumerate(group_rows.values()):
            parsed, err = res["__out__"][g_i], res["__err__"][g_i]
            flags = (parsed or {}).get("isAnomaly", [None] * len(idxs))
            for j, i in enumerate(idxs):
                out[i] = {"isAnomaly": flags[j] if j < len(flags) else None}
                errs[i] = err
        return (df.with_column(self.get("output_col"), out)
                  .with_column(self.get("error_col"), errs))

    def _local_transform(self, df: DataFrame) -> DataFrame:
        from ..ops.compile_cache import jitted

        def mad_z(v):
            import jax.numpy as jnp
            med = jnp.median(v)
            mad = jnp.median(jnp.abs(v - med)) + 1e-9
            return 0.6745 * jnp.abs(v - med) / mad

        fn = jitted("services.anomaly.mad_z", mad_z)
        vals = np.asarray(df[self.get("value_col")], dtype=np.float32)
        out = np.empty(len(df), dtype=object)
        thr = self.get("local_threshold")
        for idxs in _group_indices(df[self.get("group_col")]).values():
            z = np.asarray(fn(vals[idxs]))
            for j, i in enumerate(idxs):
                out[i] = {"isAnomaly": bool(z[j] > thr),
                          "score": float(z[j])}
        return (df.with_column(self.get("output_col"), out)
                  .with_column(self.get("error_col"),
                               object_col([None] * len(df))))
