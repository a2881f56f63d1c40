"""Fused FC + row-softmax (paper §V-C, Fig. 9 'FC layer' block).

The paper's concurrent schedule runs GEMM on TEs while PEs compute softmax on
the previous tile; on TPU the same concurrency is one fused kernel: the MXU
accumulates X@W over K blocks, and on the last K step the VPU applies the
row softmax before the tile ever leaves VMEM.

Grid: (m_blocks, k_blocks) — the full output row (N) is kept as one block so
the row reduction is local.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _fc_softmax_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _softmax():
        z = acc_ref[...] + b_ref[...].astype(jnp.float32)
        z = z - jnp.max(z, axis=-1, keepdims=True)
        p = jnp.exp(z)
        o_ref[...] = (
            p / jnp.sum(p, axis=-1, keepdims=True)
        ).astype(o_ref.dtype)


def fc_softmax(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (K, N)
    bias: Optional[jax.Array] = None,  # (N,)
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    _, n = w.shape
    bm, bk = min(bm, m), min(bk, k)
    assert m % bm == 0 and k % bk == 0
    grid = (m // bm, k // bk)
    if bias is None:
        bias = jnp.zeros((n,), x.dtype)
    kernel = functools.partial(_fc_softmax_kernel, k_steps=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
            pl.BlockSpec((bk, n), lambda i, kk: (kk, 0)),
            pl.BlockSpec((1, n), lambda i, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, bias.reshape(1, n))
