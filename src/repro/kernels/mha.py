"""Fused multi-head attention (paper §V-C MHA block) — flash-style Pallas
kernel: online softmax, scores never leave VMEM.

Grid: (batch*heads, q_blocks, kv_blocks) with kv innermost; VMEM scratch
holds the running max m, normalizer l, and fp32 output accumulator — the
direct analogue of the paper keeping the attention tile resident in L1 while
TEs compute QK^T and PV and PEs apply the softmax between them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant
from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _mha_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                kv_steps: int, bq: int, bkv: int, causal: bool, scale: float):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bkv, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bkv)
    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0
        )
        k_pos = kv_i * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_i == kv_steps - 1)
    def _finish():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def mha(
    q: jax.Array,  # (BH, Sq, D) — batch*heads flattened
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,  # (BH, Sk, D)
    *,
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bkv = min(bkv, sk)
    assert sq % bq == 0 and sk % bkv == 0
    grid = (bh, sq // bq, sk // bkv)
    scale = d**-0.5
    kernel = functools.partial(
        _mha_kernel, kv_steps=grid[2], bq=bq, bkv=bkv, causal=causal,
        scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# quantized path (int8 / fp8 q,k,v storage; dequant-on-load; fp32 softmax)
# ---------------------------------------------------------------------------

def _mha_quant_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, vs_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, kv_steps: int, bq: int,
                      bkv: int, causal: bool, scale: float):
    """Flash kernel over quantized q/k/v tiles: the (batch*head) fp32
    scales ride in as (1,1) blocks and fold into the softmax scale and the
    PV accumulate, so the online-softmax arithmetic stays fp32 — the win
    is the 2-4x smaller q/k/v stream through VMEM."""
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = qs_ref[0, 0].astype(jnp.float32)
    ks = ks_ref[0, 0].astype(jnp.float32)
    vs = vs_ref[0, 0].astype(jnp.float32)
    # dequant-on-load: scores scale by qs*ks, exact for scalar scales
    q = q_ref[0].astype(jnp.float32) * (scale * qs * ks)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0
        )
        k_pos = kv_i * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    ) * vs
    m_ref[...] = m_new

    @pl.when(kv_i == kv_steps - 1)
    def _finish():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def quantize_mha_operands(q: jax.Array, k: jax.Array, v: jax.Array,
                          precision: str):
    """Per-(batch*head) scalar scales — softmax rows mix every position of
    one head, so the scale must be uniform along S and D; per-head absmax
    is the finest grain that stays exact through the online softmax."""
    qq, qs = quant.quantize(q, precision, axis=(1, 2))
    kq, ks = quant.quantize(k, precision, axis=(1, 2))
    vq, vs = quant.quantize(v, precision, axis=(1, 2))
    to2d = lambda s: s.reshape(s.shape[0], 1)
    return qq, kq, vq, to2d(qs), to2d(ks), to2d(vs)


def mha_quant(
    q: jax.Array,  # (BH, Sq, D) float
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,  # (BH, Sk, D)
    *,
    precision: str = "int8",  # int8 | fp8 (e4m3; int8 storage fallback)
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash-MHA over int8/fp8-quantized q/k/v (per-head scales, fp32
    online softmax + accumulate).  Output stays q.dtype."""
    precision = quant.resolve_precision(precision)
    assert precision in quant.QUANTIZED, precision
    interpret = resolve_interpret(interpret)
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bkv = min(bkv, sk)
    assert sq % bq == 0 and sk % bkv == 0
    qq, kq, vq, qs, ks, vs = quantize_mha_operands(q, k, v, precision)
    grid = (bh, sq // bq, sk // bkv)
    scale = d**-0.5
    kernel = functools.partial(
        _mha_quant_kernel, kv_steps=grid[2], bq=bq, bkv=bkv, causal=causal,
        scale=scale,
    )
    scale_spec = pl.BlockSpec((1, 1), lambda b, i, j: (b, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            scale_spec, scale_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qq, kq, vq, qs, ks, vs)


def mha_quant_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  precision: str = "int8", causal: bool = True) -> jax.Array:
    """Pure-jnp quantized MHA (XLA fast path off-TPU): same arithmetic —
    quantized storage, dequant-on-load, fp32 softmax."""
    precision = quant.resolve_precision(precision)
    qq, kq, vq, qs, ks, vs = quantize_mha_operands(q, k, v, precision)
    d = q.shape[-1]
    qf = qq.astype(jnp.float32) * (qs * ks * d**-0.5)[..., None]
    s = jnp.einsum("bqd,bkd->bqk", qf, kq.astype(jnp.float32))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, vq.astype(jnp.float32))
    return (out * vs[..., None]).astype(q.dtype)
