"""KV quantization helpers — the SANCTIONED quant/dequant primitives.

The quantized KV data plane stores K/V pages as int8 (or
``float8_e4m3fn`` where the platform has it) plus a per-position
per-head scale array, and dequantizes INSIDE the paged-attention kernel
(`ops/paged_attention.py`). Every writer — ``paged_scatter_rows``
(prefill), ``_paged_writeback`` (gather impl), ``_pool_write_rows``
(mesh mount) and the fused kernel's in-launch scatter — must produce
bit-identical bytes for the same rows, so they all quantize through
:func:`quantize_kv` below. tpulint TPU018 (``unscaled-quant-cast``)
enforces exactly this: a bare ``.astype(int8/fp8)`` on a KV/activation
tensor anywhere outside this module is flagged.

Scheme: symmetric per-(position, head) absmax scaling over the head
dimension. For a row ``x`` of shape ``(..., hd)``::

    scale = amax(|x|, axis=-1) / qmax        (1.0 where amax == 0)
    q     = clip(round(x / scale), -qmax, qmax).astype(store)
    x'    = q * scale

Scales are stored in **bfloat16**, not f32 — the byte ratio is what the
whole tentpole is about: at ``hd == 64`` a bf16 K/V position is 128
bytes; int8 values + a bf16 scale are 66 (1.94x), while an f32 scale
would make it 68 (1.88x) and miss the 1.9x HBM target. The stored
(rounded) scale is also the one used for the forward division, so
``dequantize_kv(quantize_kv(x))`` reproduces exactly what the kernel
reads.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

__all__ = ["quantize_kv", "dequantize_kv", "resolve_kv_dtype",
           "kv_store_dtype", "kv_qmax", "supports_fp8", "SCALE_DTYPE",
           "kv_bytes_per_position"]

#: dtype of the per-(page, head, position) scale arrays. bf16, so a
#: quantized position costs hd + 2 bytes against bf16's 2*hd.
SCALE_DTYPE = jnp.bfloat16

#: canonical kv_dtype names -> canonical form (None = unquantized bf16
#: pages, the oracle path)
_CANON = {None: None, "": None, "none": None, "bf16": None,
          "bfloat16": None, "int8": "int8", "fp8": "fp8",
          "float8": "fp8", "float8_e4m3fn": "fp8", "e4m3": "fp8"}

#: symmetric clip bound per store dtype: int8 uses +-127 (the -128 code
#: is never produced, keeping the scheme symmetric); e4m3fn saturates
#: at +-448
_QMAX_INT8 = 127.0
_QMAX_FP8 = 448.0


def supports_fp8() -> bool:
    """Whether ``kv_dtype="fp8"`` is available: the installed jax has
    ``float8_e4m3fn``."""
    return True


def resolve_kv_dtype(kv_dtype) -> Optional[str]:
    """Canonicalize a ``kv_dtype`` knob value to ``"int8"``, ``"fp8"``
    or None (bf16 pages). Raises on unknown names."""
    key = kv_dtype
    if isinstance(key, str):
        key = key.strip().lower()
    if key not in _CANON:
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r} (choose 'bf16', 'int8' or 'fp8')")
    return _CANON[key]


def kv_store_dtype(kv_dtype: Optional[str]):
    """The jnp dtype quantized pages are stored in, or None for the
    unquantized (bf16 oracle) representation."""
    canon = resolve_kv_dtype(kv_dtype)
    if canon is None:
        return None
    if canon == "int8":
        return jnp.int8
    return jnp.float8_e4m3fn


def kv_qmax(dtype) -> float:
    """Symmetric clip bound for a quantized store dtype — derived from
    the POOL BUFFER dtype inside jitted code, so no static string rides
    through the trace."""
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.int8):
        return _QMAX_INT8
    if d == jnp.dtype(jnp.float8_e4m3fn):
        return _QMAX_FP8
    raise ValueError(f"not a quantized KV store dtype: {dtype!r}")


def quantize_kv(x, store_dtype):
    """Quantize ``x`` (..., hd) to ``(q, scale)`` with per-(...,) head-row
    absmax scales: ``q`` has ``x``'s shape in ``store_dtype``; ``scale``
    drops the last axis and is :data:`SCALE_DTYPE`. The division uses
    the ROUNDED (stored) scale so every writer and the in-kernel dequant
    agree bit-for-bit."""
    qm = kv_qmax(store_dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax / qm, 1.0).astype(SCALE_DTYPE)
    y = xf / scale.astype(jnp.float32)[..., None]
    if jnp.dtype(store_dtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(y), -qm, qm).astype(store_dtype)
    else:
        q = jnp.clip(y, -qm, qm).astype(store_dtype)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Reconstruct ``q * scale`` (scale broadcast over the trailing head
    dimension) in ``dtype`` — exactly the product the Pallas kernel
    forms in VMEM after its page DMA."""
    out = q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    return out.astype(dtype)


def kv_bytes_per_position(heads: int, head_dim: int, value_dtype,
                          quantized: bool) -> int:
    """HBM bytes one cached K+V position costs across both tensors of
    ONE layer: ``2 * heads * (hd * itemsize + scale)``. This is the
    number the engine's per-tick byte accounting and the pool's
    residency reservation both derive from, so the bench's
    ``hbm_bytes_saved_per_step`` counter-assert measures the layout that
    is actually allocated."""
    item = jnp.dtype(value_dtype).itemsize
    scale = jnp.dtype(SCALE_DTYPE).itemsize if quantized else 0
    return 2 * int(heads) * (int(head_dim) * item + scale)
