"""TE GEMM — the RedMulE tensor engine (paper §III-B) adapted to the TPU MXU.

RedMulE dataflow: output-stationary — a (R x C(P+1)) tile of Z stays in the
accumulation registers while X rows / W columns stream through; the streamer
double-buffers the next tiles (X/W/Y buffers) to hide the multi-cycle L1
interconnect latency.

TPU mapping (DESIGN.md §2):
  Z tile (bm x bn)        -> fp32 VMEM scratch accumulator (output-stationary)
  X/W streamer + ROB      -> Pallas grid pipeline: the next (bm x bk)/(bk x bn)
                             blocks are DMA'd HBM->VMEM while the MXU works
  burst grouping          -> lane-aligned (multiple-of-128) block shapes
  Kung balance (Eq. 2-3)  -> pick_block_shape solves the same inequality for
                             VMEM budget + MXU alignment

The kernel also supports the paper's "concurrent PE" epilogues (bias, ReLU /
SiLU / row-softmax) computed on the VPU while the MXU streams the next tile —
the Fig. 9/10 concurrency realized as fusion.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.balance import gemm_tile_balance, tile_vmem_bytes
from repro.core.machine import TPU_V5E, Machine
from repro.kernels import quant, tune
from repro.kernels.runtime import resolve_interpret


def _dtype_key(dtype_or_bytes) -> tuple[str, int]:
    """(tune-cache label, itemsize).  Ints are the legacy ``dtype_bytes``
    API and keep their ``b{n}`` label; dtypes key on the dtype *name* so
    the 1-byte dtypes (int8 vs float8_e4m3fn) never collide."""
    if isinstance(dtype_or_bytes, int):
        return f"b{dtype_or_bytes}", dtype_or_bytes
    dt = jnp.dtype(dtype_or_bytes)
    return dt.name, dt.itemsize


def pick_block_shape(
    m: int, n: int, k: int, dtype_bytes=2,
    machine: Machine = TPU_V5E, vmem_budget: Optional[int] = None,
) -> tuple[int, int, int]:
    """Measured-or-modeled (bm, bn, bk).

    A winner persisted by the :mod:`repro.kernels.tune` autotuner for this
    (shape, dtype, backend) takes precedence (latency objective first,
    then energy — a measured winner either way); otherwise fall back to
    the static heuristic: search multiples of 128 (MXU dimension / lane
    width: the 'burst' unit), largest-first, requiring:
      * double-buffered tile footprint <= VMEM budget (paper: X/W/Y buffers)
      * Kung's inequality (Eq. 2-3) holds for the HBM->VMEM stream

    ``dtype_bytes`` accepts a dtype (preferred — keys the cache on the
    dtype name) or a legacy byte count.
    """
    label, dtype_bytes = _dtype_key(dtype_bytes)
    cached = tune.cached_choice("te_gemm", (m, n, k), label)
    if cached is None:
        cached = tune.cached_choice("te_gemm", (m, n, k), label,
                                    objective="energy")
    if cached is None and dtype_bytes >= 2 and not label.startswith("b"):
        # pre-dtype-name caches keyed b2/b4; 1-byte legacy keys were
        # ambiguous (the int8/fp8 collision this keying fixes) — skip
        cached = tune.cached_choice("te_gemm", (m, n, k),
                                    f"b{dtype_bytes}")
    if cached is not None and len(cached) == 3:
        bm, bn, bk = (min(c, d) for c, d in zip(cached, (m, n, k)))
        if m % bm == 0 and n % bn == 0 and k % bk == 0:
            return (bm, bn, bk)
    budget = vmem_budget or machine.fast_mem_bytes // 2
    cands = [512, 256, 128]
    best = None
    for bm in cands:
        for bn in cands:
            for bk in cands:
                if bm > m and bm != 128 or bn > n and bn != 128:
                    continue
                if tile_vmem_bytes(bm, bn, bk, dtype_bytes) > budget:
                    continue
                rep = gemm_tile_balance(bm, bn, bk, dtype_bytes, machine)
                score = (rep.balanced, bm * bn * bk)
                if best is None or score > best[0]:
                    best = (score, (bm, bn, bk))
    assert best is not None
    return best[1]


def _te_gemm_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                    epilogue: str, has_bias: bool):
    """Grid: (m_blocks, n_blocks, k_steps); K innermost (output-stationary)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU work: accumulate the partial dot-product (RedMulE inner loop)
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        # "PE" (VPU) work fused with the TE (paper Fig. 9 concurrency)
        if epilogue == "relu":
            acc = jnp.maximum(acc, 0.0)
        elif epilogue == "silu":
            acc = acc * jax.nn.sigmoid(acc)
        elif epilogue == "softmax":  # row-wise over this n-block
            acc = jax.nn.softmax(acc, axis=-1)
        o_ref[...] = acc.astype(o_ref.dtype)


def te_gemm(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (K, N)
    bias: Optional[jax.Array] = None,  # (N,)
    *,
    epilogue: str = "none",  # none | relu | silu | softmax(row within block)
    block_shape: Optional[tuple[int, int, int]] = None,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = block_shape or pick_block_shape(m, n, k, x.dtype)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) not divisible by blocks ({bm},{bn},{bk})"
    )
    if epilogue == "softmax":
        assert bn == n, "row-softmax epilogue needs the full row in one block"
    grid = (m // bm, n // bn, k // bk)
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((n,), x.dtype)
    bias2d = bias.reshape(1, n)

    kernel = functools.partial(
        _te_gemm_kernel, k_steps=grid[2], epilogue=epilogue,
        has_bias=has_bias,
    )
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, bias2d)


# ---------------------------------------------------------------------------
# quantized path (int8 / fp8 storage, fp32 accumulate, dequant epilogue)
# ---------------------------------------------------------------------------

def _te_gemm_quant_kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, o_ref,
                          acc_ref, *, k_steps: int, epilogue: str,
                          has_bias: bool, int_acc: bool):
    """Same grid/dataflow as ``_te_gemm_kernel``; the operands arrive
    already quantized (int8 or fp8) with their per-row / per-column fp32
    scales, the accumulator is int32 (int8 MXU path) or fp32 (fp8, which
    models dequant-on-load), and the epilogue applies the rank-1 scale
    product before bias/activation — the paper's "concurrent PE" work."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if int_acc:
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.int32
        )
    else:
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        # dequant: scales are per-row (xs) x per-col (ws), a rank-1
        # factorization that commutes with the dot — exact, not approximate
        acc = (acc_ref[...].astype(jnp.float32)
               * xs_ref[...].astype(jnp.float32)
               * ws_ref[...].astype(jnp.float32))
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        if epilogue == "relu":
            acc = jnp.maximum(acc, 0.0)
        elif epilogue == "silu":
            acc = acc * jax.nn.sigmoid(acc)
        elif epilogue == "softmax":
            acc = jax.nn.softmax(acc, axis=-1)
        o_ref[...] = acc.astype(o_ref.dtype)


def quantize_gemm_operands(x: jax.Array, w: jax.Array, precision: str):
    """-> (xq, wq, x_scale (M,1), w_scale (1,N)) for the quantized kernel.

    Per-row activation scales and per-column weight scales: each output
    element sees exactly one (xs, ws) pair, so dequant is exact w.r.t.
    the quantization grid.
    """
    xq, xs = quant.quantize(x, precision, axis=1)
    wq, ws = quant.quantize(w, precision, axis=0)
    return xq, wq, xs, ws


def te_gemm_quant(
    x: jax.Array,  # (M, K) float
    w: jax.Array,  # (K, N) float
    bias: Optional[jax.Array] = None,  # (N,)
    *,
    precision: str = "int8",  # int8 | fp8 (e4m3; int8 storage fallback)
    epilogue: str = "none",
    block_shape: Optional[tuple[int, int, int]] = None,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``te_gemm`` over quantized operands: int8/fp8 storage halves (or
    quarters) the X/W stream traffic, the MXU accumulates into int32/fp32,
    and the fp32 dequant epilogue restores the scale before bias and
    activation.  Output stays float (default: x.dtype)."""
    precision = quant.resolve_precision(precision)
    assert precision in quant.QUANTIZED, precision
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    xq, wq, xs, ws = quantize_gemm_operands(x, w, precision)
    q_dtype = xq.dtype
    int_acc = q_dtype == jnp.int8
    bm, bn, bk = block_shape or pick_block_shape(m, n, k, q_dtype)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) not divisible by blocks ({bm},{bn},{bk})"
    )
    if epilogue == "softmax":
        assert bn == n, "row-softmax epilogue needs the full row in one block"
    grid = (m // bm, n // bn, k // bk)
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    bias2d = bias.reshape(1, n)

    kernel = functools.partial(
        _te_gemm_quant_kernel, k_steps=grid[2], epilogue=epilogue,
        has_bias=has_bias, int_acc=int_acc,
    )
    out_dtype = out_dtype or x.dtype
    acc_dtype = jnp.int32 if int_acc else jnp.float32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, wq, xs, ws, bias2d)


def te_gemm_quant_jnp(
    x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None, *,
    precision: str = "int8", epilogue: str = "none", out_dtype=None,
) -> jax.Array:
    """Pure-jnp quantized GEMM (the XLA fast path off-TPU): identical
    arithmetic to ``te_gemm_quant`` — quantized dot, wide accumulate,
    rank-1 dequant, then bias/activation."""
    precision = quant.resolve_precision(precision)
    xq, wq, xs, ws = quantize_gemm_operands(x, w, precision)
    if xq.dtype == jnp.int8:
        acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    else:
        acc = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32))
    z = acc.astype(jnp.float32) * xs * ws
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if epilogue == "relu":
        z = jnp.maximum(z, 0.0)
    elif epilogue == "silu":
        z = z * jax.nn.sigmoid(z)
    elif epilogue == "softmax":
        z = jax.nn.softmax(z, axis=-1)
    return z.astype(out_dtype or x.dtype)
