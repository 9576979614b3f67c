"""Centralized accelerator detection.

Parity role: the reference picks its execution provider by probing device
strings in one place (``deep-learning/src/main/scala/com/microsoft/azure/
synapse/ml/onnx/ONNXModel.scala:293-303`` — CUDA vs CPU EP selection).
Here every TPU gate (Pallas interpret mode, kernel autotuning, bench
labeling) funnels through :func:`is_tpu`, so what the process believes about
its device is decided — and a misdetection is visible — in exactly one place.

A backend that fails to come up is an error, not "not a TPU": :func:`is_tpu`
lets the failure propagate instead of steering every kernel gate into
interpret mode, ``segment_sum`` and dense attention.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

__all__ = ["device_info", "is_tpu", "tpu_generation", "generation_from_kind",
           "force_cpu"]


def force_cpu(virtual_devices: Optional[int] = None):
    """Pin this process AND its children to the XLA CPU backend; returns
    the jax module.

    Sets ``JAX_PLATFORMS=cpu`` in ``os.environ`` (inherited by every child
    process, so none of them loads the TPU library, which one process at a
    time may hold). Must be called before anything initializes a backend
    (importing jax is fine; running a computation is not).

    ``virtual_devices=N`` also requests an N-device virtual CPU topology
    (``--xla_force_host_platform_device_count``) for mesh smoke tests —
    honored only if no backend is live and the flag isn't already set.
    """
    if virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{int(virtual_devices)}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax

_CACHE: Optional[Tuple[str, str]] = None

#: ordered (longest-match-first) generation keys — v5p before v5
_GENERATIONS = ("v6", "v5p", "v5", "v4", "v3", "v2")


def generation_from_kind(device_kind: str) -> Optional[str]:
    """Pure-string generation key from a raw device_kind, or None."""
    kind = device_kind.lower()
    for key in _GENERATIONS:
        if key in kind:
            return key
    return None


def device_info() -> Tuple[str, str]:
    """(platform, device_kind) of the default backend's first device, as
    JAX reports them. Cached after first success — the default backend
    cannot change within a process."""
    global _CACHE
    if _CACHE is None:
        import jax
        d = jax.devices()[0]
        _CACHE = (str(d.platform or ""), str(d.device_kind or ""))
    return _CACHE


def is_tpu() -> bool:
    """True when the default backend is a TPU.

    The ``MMLSPARK_TPU_FORCE_PLATFORM`` env override (``tpu``/``cpu``, for
    tests) wins; otherwise the first device's platform decides. A backend
    that cannot initialize raises here — it is never read as "not a TPU".
    """
    forced = os.environ.get("MMLSPARK_TPU_FORCE_PLATFORM")
    if forced:
        return forced.lower() == "tpu"
    return device_info()[0].lower() == "tpu"


def tpu_generation() -> Optional[str]:
    """Generation key ("v6" / "v5p" / "v5" / "v4" / ...) parsed from
    device_kind, or None off-TPU — the lookup key for peak-FLOPs tables."""
    if not is_tpu():
        return None
    return generation_from_kind(device_info()[1])
