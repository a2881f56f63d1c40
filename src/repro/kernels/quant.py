"""Quantization core for the low-precision (int8/fp8) kernel paths.

TensorPool's headline is efficiency, not raw FLOPS: quantized activation /
LLR datapaths are the standard next step in baseband silicon (int8 NPU
baseband, arXiv 2607.04224).  This module holds the one set of precision
policies and scale/quantize/dequantize helpers every quantized kernel path
shares, so the parity tests, the energy model, and the tune-cache keys all
agree on what "int8" or "fp8" means:

* **Precision names** — ``fp32 | fp16 | bf16 | int8 | fp8``.  ``fp8`` means
  e4m3 (:data:`jnp.float8_e4m3fn`).
* **Scales** — symmetric, absmax-based, fp32, computed per-axis (per-row
  activations / per-column weights for GEMM, per-(batch*head) for MHA) and
  kept *outside* the quantized tensor so dequant is a rank-1 multiply in
  the fp32 epilogue.
* **LLR grids** — demapper LLRs quantize onto a fixed symmetric int8 grid
  (clip at ``LLR_CLIP``); layered min-sum is scale-equivariant, so the
  int8 decoder state dequantizes with the same scalar.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# e4m3 "fn" variant: finite-only, max normal 448
FP8_DTYPE = jnp.float8_e4m3fn
FP8_MAX = 448.0
INT8_MAX = 127.0

# Demapper LLR saturation: max-log LLRs at the registered operating points
# live well inside +-20 (|llr| ~ d^2/nv); one fixed grid keeps the int8
# step identical across slots so BLER curves stay reproducible.
LLR_CLIP = 20.0

PRECISIONS = ("fp32", "fp16", "bf16", "int8", "fp8")
QUANTIZED = ("int8", "fp8")

_ALIASES = {
    "float32": "fp32", "float16": "fp16", "bfloat16": "bf16",
    "fp8e4m3": "fp8", "e4m3": "fp8", "float8_e4m3fn": "fp8",
    None: "fp32", "none": "fp32",
}

_STORAGE = {
    "fp32": jnp.float32,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
}


def resolve_precision(precision: Optional[str]) -> str:
    """Canonical precision name; None -> fp32."""
    p = precision.lower() if isinstance(precision, str) else precision
    p = _ALIASES.get(p, p)
    if p not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; have {PRECISIONS}"
        )
    return p


def is_quantized(precision: Optional[str]) -> bool:
    return resolve_precision(precision) in QUANTIZED


def storage_dtype(precision: Optional[str]):
    """The jnp dtype quantized values are *stored* in."""
    p = resolve_precision(precision)
    return FP8_DTYPE if p == "fp8" else _STORAGE[p]


def itemsize(precision: Optional[str]) -> int:
    """Modeled storage bytes per element (1 for both 1-byte policies)."""
    p = resolve_precision(precision)
    return 1 if p in QUANTIZED else jnp.dtype(_STORAGE[p]).itemsize


def dtype_name(dtype) -> str:
    """Canonical dtype label for tune-cache keys: ``int8`` and
    ``float8_e4m3fn`` must never share a key (both are 1-byte)."""
    return jnp.dtype(dtype).name


def precision_of_dtype(dtype) -> str:
    """Map a jnp dtype back onto a precision name (any float8 -> fp8)."""
    name = jnp.dtype(dtype).name
    if name.startswith("float8"):
        return "fp8"
    return resolve_precision(name)


# ---------------------------------------------------------------------------
# tensor quantization (symmetric absmax, external fp32 scales)
# ---------------------------------------------------------------------------

def _absmax(x: jax.Array, axis) -> jax.Array:
    ax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    return jnp.maximum(ax, 1e-12)  # all-zero slices: scale stays finite


def quantize(x: jax.Array, precision: str, axis=None):
    """-> (q, scale) with ``dequantize(q, scale) ~= x``.

    ``axis`` is reduced for the absmax (keepdims), so the scale broadcasts
    back against ``x``; ``axis=None`` gives one scalar scale.
    """
    p = resolve_precision(precision)
    assert p in QUANTIZED, f"quantize() is for int8/fp8, got {p!r}"
    dt = storage_dtype(p)
    amax = _absmax(x, axis)
    if dt == jnp.int8:
        scale = amax / INT8_MAX
        q = jnp.clip(
            jnp.round(x.astype(jnp.float32) / scale), -INT8_MAX, INT8_MAX
        ).astype(jnp.int8)
    else:  # fp8 e4m3: scale so the slice absmax lands on the format max
        scale = amax / FP8_MAX
        q = (x.astype(jnp.float32) / scale).astype(dt)
    return q, scale.astype(jnp.float32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def fake_quant(x: jax.Array, precision: Optional[str], axis=None
               ) -> jax.Array:
    """Round-trip ``x`` through the precision's storage grid (same dtype
    out).  fp32 passes through; fp16/bf16 cast through the half dtype."""
    p = resolve_precision(precision)
    if p == "fp32":
        return x
    if p in ("fp16", "bf16"):
        return x.astype(_STORAGE[p]).astype(x.dtype)
    q, scale = quantize(x, p, axis=axis)
    return dequantize(q, scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# LLR quantization (fixed symmetric grid — what baseband silicon ships)
# ---------------------------------------------------------------------------

def llr_scale(clip: float = LLR_CLIP) -> float:
    """LLR units per int8 code (a python float: kernels bake it in
    statically)."""
    return clip / INT8_MAX


def quantize_llr(llr: jax.Array, clip: float = LLR_CLIP):
    """-> (q int8, scalar fp32 scale); saturates at +-clip."""
    s = llr_scale(clip)
    q = jnp.clip(
        jnp.round(llr.astype(jnp.float32) / s), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)
    return q, jnp.float32(s)


def dequantize_llr(q: jax.Array, scale) -> jax.Array:
    return q.astype(jnp.float32) * scale


def fake_quant_llr(llr: jax.Array, precision: Optional[str],
                   clip: float = LLR_CLIP) -> jax.Array:
    """LLRs round-tripped through the precision's grid (int8 grid for both
    int8 and fp8 — LLR state is integer in silicon either way)."""
    p = resolve_precision(precision)
    if p == "fp32":
        return llr
    if p in ("fp16", "bf16"):
        return llr.astype(_STORAGE[p]).astype(llr.dtype)
    q, s = quantize_llr(llr, clip)
    return dequantize_llr(q, s).astype(llr.dtype)


# ---------------------------------------------------------------------------
# saturating integer arithmetic (int8 LLR state kept in int32 lanes)
# ---------------------------------------------------------------------------

def sat8(x: jax.Array) -> jax.Array:
    """Saturate int32 values onto the symmetric int8 range [-127, 127]."""
    return jnp.clip(x, -127, 127)


def scale_q8(mag: jax.Array, factor: float) -> jax.Array:
    """Integer multiply by a [0,1) factor: (mag * round(f*256)) >> 8 —
    the fixed-point damping a hardware min-sum datapath uses."""
    ifac = int(round(factor * 256.0))
    return (mag * ifac) >> 8
