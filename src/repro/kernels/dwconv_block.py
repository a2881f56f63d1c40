"""Fused depthwise-separable conv block (paper §V-C, Fig. 9):
depthwise 3x3 conv -> pointwise 1x1 conv (GEMM) -> layernorm -> ReLU.

Paper mapping: the pointwise conv is TE work (GEMM with accumulation along
depth), the depthwise conv + LN + ReLU are PE work run concurrently; here the
whole block is one Pallas kernel — the depthwise stage (VPU shifts+FMAs)
feeds the MXU pointwise GEMM in VMEM, and LN+ReLU run on the accumulated
output tile before it is written back.

Input is pre-padded spatially: x (B, H+2, W+2, C); filters dw (3, 3, C),
pw (C, F); gamma/beta (F,).  Grid: (B, c_blocks) with C innermost —
the (H*W, F) accumulator is output-stationary in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _dwconv_kernel(x_ref, dw_ref, pw_ref, g_ref, b_ref, o_ref, acc_ref, *,
                   h: int, w: int, c_steps: int, eps: float):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)  # (H+2, W+2, bc)
    dw = dw_ref[...].astype(jnp.float32)  # (3, 3, bc)
    # depthwise 3x3 (VPU: shifted multiply-accumulate)
    y = jnp.zeros((h, w, x.shape[-1]), jnp.float32)
    for di in range(3):
        for dj in range(3):
            y = y + x[di : di + h, dj : dj + w, :] * dw[di, dj][None, None, :]
    # pointwise conv = GEMM over the channel block (MXU), accumulated
    acc_ref[...] += jnp.dot(
        y.reshape(h * w, -1), pw_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ci == c_steps - 1)
    def _ln_relu():
        acc = acc_ref[...]  # (H*W, F)
        mu = jnp.mean(acc, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(acc - mu), axis=-1, keepdims=True)
        z = (acc - mu) * jax.lax.rsqrt(var + eps)
        z = z * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
        o_ref[0] = jnp.maximum(z, 0.0).reshape(o_ref.shape[1:]).astype(
            o_ref.dtype
        )


def dwconv_block(
    x: jax.Array,  # (B, H+2, W+2, C) pre-padded
    dw: jax.Array,  # (3, 3, C)
    pw: jax.Array,  # (C, F)
    gamma: jax.Array,  # (F,)
    beta: jax.Array,  # (F,)
    *,
    bc: int = 128,
    eps: float = 1e-5,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, hp, wp, c = x.shape
    h, w = hp - 2, wp - 2
    f = pw.shape[1]
    bc = min(bc, c)
    assert c % bc == 0
    grid = (b, c // bc)
    kernel = functools.partial(
        _dwconv_kernel, h=h, w=w, c_steps=grid[1], eps=eps
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, bc), lambda bi, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((3, 3, bc), lambda bi, ci: (0, 0, ci)),
            pl.BlockSpec((bc, f), lambda bi, ci: (ci, 0)),
            pl.BlockSpec((1, f), lambda bi, ci: (0, 0)),
            pl.BlockSpec((1, f), lambda bi, ci: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, w, f), lambda bi, ci: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((h * w, f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dw, pw, gamma.reshape(1, f), beta.reshape(1, f))
