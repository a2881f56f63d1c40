"""Fused classical-receiver kernels (paper §V-B on the §III hardware).

TensorPool's headline utilization comes from fusing the RAN tensor chain so
intermediates stay in the 4 MiB L1.  These kernels give the *classical*
receiver stages the same treatment the neural hot paths already get:

* ``mmse_detect_demap`` — equalize→demap in one pass.  Per (batch-row,
  subcarrier) tile it forms the regularized Gram matrix, solves the small
  MMSE system (n_tx <= 4) in-register via explicit Gauss elimination, and
  emits unbiased max-log LLRs — without ever materializing ``h_eff`` /
  Gram / equalized-symbol grids in HBM.
* ``ls_che`` — fused LS channel estimation: DMRS comb extract → per-pilot
  divide → frequency interpolation, folded into a banded complex GEMM per
  subcarrier tile against a precomputed interpolation operator (TE work
  instead of PE gather/lerp).  A tile reads only the pilots it needs, so
  its VMEM (:func:`ls_che_vmem_bytes`) does not grow with the carrier.

Pallas has no complex dtype, so everything runs in a split-complex planar
FP32 layout: real/imag components (and the small antenna dims) are stacked
on the leading axis while (rows, subcarriers) occupy the tiled trailing
axes.  The arithmetic lives in ``_detect_demap_core``, shared verbatim by

* the Pallas kernel (compiled Mosaic on TPU, interpreter mode in tests), and
* a plain-jnp path where XLA fuses the same element-wise chain — the fast
  route off-TPU, since interpret-mode Pallas is orders of magnitude slower.

``use_pallas=None`` auto-selects per backend (the same policy as
``runtime.resolve_interpret``).  Subcarrier tile shapes are resolved
through the :mod:`repro.kernels.tune` cache before static defaults.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant, tune
from repro.kernels.runtime import resolve_interpret


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    """None -> Pallas only where it compiles to Mosaic (TPU)."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


_LANE, _SUBLANE = 128, 8  # Mosaic's f32 tile


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _cmul(ar, ai, br, bi):
    """(ar + i*ai) * (br + i*bi) in split-complex form."""
    return ar * br - ai * bi, ar * bi + ai * br


# ---------------------------------------------------------------------------
# fused equalize -> demap: shared split-complex math
# ---------------------------------------------------------------------------

def _bit_of_table(n_levels: int, nb: int):
    """bit_of[p][j]: bit p (MSB first) of the axis-level index j."""
    return [[(j >> (nb - 1 - p)) & 1 for j in range(n_levels)]
            for p in range(nb)]


def _detect_demap_core(yr, yi, hr, hi, nv, levels: Sequence[float],
                       norm: float, nb: int):
    """One fused pass: Gram -> Gauss solve -> unbias -> max-log LLRs.

    ``yr/yi`` are per-rx lists of arrays; ``hr/hi`` are [rx][tx] nested
    lists broadcastable against them.  All loops below are over the static
    antenna/constellation dims (n_tx <= 4, <= 8 levels), so the whole chain
    unrolls into straight-line VPU code — every intermediate is a live
    register tile, nothing round-trips through memory.

    Returns (xr, xi, nve, llr): per-tx lists; ``llr[t]`` is the
    2*nb per-bit list (real-axis bits first, matching ``Modem.demod_llr``).
    """
    n_rx, n_tx = len(yr), len(hr[0])
    n_lv = len(levels)

    # Gram G = H^H H and rhs b = H^H y
    gr = [[None] * n_tx for _ in range(n_tx)]
    gi = [[None] * n_tx for _ in range(n_tx)]
    for t in range(n_tx):
        for u in range(n_tx):
            sr, si = 0.0, 0.0
            for r in range(n_rx):
                pr, pi = _cmul(hr[r][t], -hi[r][t], hr[r][u], hi[r][u])
                sr, si = sr + pr, si + pi
            gr[t][u], gi[t][u] = sr, si

    # A = G + nv I; augmented RHS [H^H y | G] so one elimination yields both
    # the filter output and the bias diagonal mu = diag(A^-1 G)
    ar = [[gr[t][u] + nv if t == u else gr[t][u] + 0.0
           for u in range(n_tx)] for t in range(n_tx)]
    ai = [[gi[t][u] + 0.0 for u in range(n_tx)] for t in range(n_tx)]
    nrhs = 1 + n_tx
    br = [[None] * nrhs for _ in range(n_tx)]
    bi = [[None] * nrhs for _ in range(n_tx)]
    for t in range(n_tx):
        sr, si = 0.0, 0.0
        for r in range(n_rx):
            pr, pi = _cmul(hr[r][t], -hi[r][t], yr[r], yi[r])
            sr, si = sr + pr, si + pi
        br[t][0], bi[t][0] = sr, si
        for u in range(n_tx):
            br[t][1 + u], bi[t][1 + u] = gr[t][u], gi[t][u]

    # Gauss elimination, no pivoting (A is Hermitian positive definite)
    for kd in range(n_tx):
        dr, di = ar[kd][kd], ai[kd][kd]
        den = dr * dr + di * di
        ivr, ivi = dr / den, -di / den
        for i in range(kd + 1, n_tx):
            fr, fi = _cmul(ar[i][kd], ai[i][kd], ivr, ivi)
            for u in range(kd, n_tx):
                pr, pi = _cmul(fr, fi, ar[kd][u], ai[kd][u])
                ar[i][u], ai[i][u] = ar[i][u] - pr, ai[i][u] - pi
            for j in range(nrhs):
                pr, pi = _cmul(fr, fi, br[kd][j], bi[kd][j])
                br[i][j], bi[i][j] = br[i][j] - pr, bi[i][j] - pi
    zr = [[None] * nrhs for _ in range(n_tx)]
    zi = [[None] * nrhs for _ in range(n_tx)]
    for kd in range(n_tx - 1, -1, -1):
        dr, di = ar[kd][kd], ai[kd][kd]
        den = dr * dr + di * di
        ivr, ivi = dr / den, -di / den
        for j in range(nrhs):
            sr, si = br[kd][j], bi[kd][j]
            for u in range(kd + 1, n_tx):
                pr, pi = _cmul(ar[kd][u], ai[kd][u], zr[u][j], zi[u][j])
                sr, si = sr - pr, si - pi
            zr[kd][j], zi[kd][j] = _cmul(sr, si, ivr, ivi)

    # unbias (mu_t = Re[A^-1 G]_tt) + per-axis max-log LLRs
    scale = float(np.sqrt(norm))
    bit_of = _bit_of_table(n_lv, nb)
    xr, xi, nve, llr = [], [], [], []
    for t in range(n_tx):
        mu = jnp.clip(zr[t][1 + t], 1e-6, 1.0 - 1e-6)
        ux, uy = zr[t][0] / mu, zi[t][0] / mu
        ne = (1.0 - mu) / mu
        nvs = jnp.maximum(ne * norm, 1e-6)
        xr.append(ux)
        xi.append(uy)
        nve.append(ne)
        bits = []
        for comp in (ux, uy):
            d = [(comp * scale - lv) ** 2 for lv in levels]
            for p in range(nb):
                d0 = d1 = None
                for j in range(n_lv):
                    if bit_of[p][j]:
                        d1 = d[j] if d1 is None else jnp.minimum(d1, d[j])
                    else:
                        d0 = d[j] if d0 is None else jnp.minimum(d0, d[j])
                bits.append((d0 - d1) / nvs)
        llr.append(bits)
    return xr, xi, nve, llr


def _hard_axis(comp, levels: Sequence[float], scale: float):
    """Nearest per-axis constellation level of ``comp`` (unit-power
    domain), unrolled over the static level set — the hard re-modulation
    of one SIC cancellation stage.  Equivalent to thresholding the
    per-axis max-log LLRs for gray square QAM."""
    v = comp * scale
    best = levels[0] + 0.0 * v
    best_d = (v - levels[0]) ** 2
    for lv in levels[1:]:
        d = (v - lv) ** 2
        best = jnp.where(d < best_d, lv, best)
        best_d = jnp.minimum(d, best_d)
    return best / scale


def _sic_core(yr, yi, hr, hi, nv, levels: Sequence[float], norm: float,
              nb: int):
    """Successive interference cancellation reusing the in-register MMSE
    solve of :func:`_detect_demap_core` per stage.

    Stage ``k`` solves the suffix system over streams ``k..n_tx-1``
    (the Gram/Gauss chain shrinks every stage), keeps stream ``k``'s
    unbiased estimate + LLRs, hard-remodulates it on the modem grid, and
    subtracts its reconstructed contribution from the residual — all in
    the same live-register tile; the residual grids never round-trip.
    Streams cancel in index order (strongest first by scenario
    convention).  Same return contract as :func:`_detect_demap_core`.
    """
    n_rx, n_tx = len(yr), len(hr[0])
    scale = float(np.sqrt(norm))
    yr, yi = list(yr), list(yi)
    xr_o, xi_o, nve_o, llr_o = [], [], [], []
    for k in range(n_tx):
        sub_hr = [[hr[r][t] for t in range(k, n_tx)] for r in range(n_rx)]
        sub_hi = [[hi[r][t] for t in range(k, n_tx)] for r in range(n_rx)]
        xr, xi, nve, llr = _detect_demap_core(
            yr, yi, sub_hr, sub_hi, nv, levels, norm, nb
        )
        xr_o.append(xr[0])
        xi_o.append(xi[0])
        nve_o.append(nve[0])
        llr_o.append(llr[0])
        if k < n_tx - 1:
            hxr = _hard_axis(xr[0], levels, scale)
            hxi = _hard_axis(xi[0], levels, scale)
            for r in range(n_rx):
                cr, ci = _cmul(hr[r][k], hi[r][k], hxr, hxi)
                yr[r] = yr[r] - cr
                yi[r] = yi[r] - ci
    return xr_o, xi_o, nve_o, llr_o


# ---------------------------------------------------------------------------
# fused equalize -> demap: jnp path (off-TPU fast route)
# ---------------------------------------------------------------------------

def _demap_jnp(core, y, h, noise_var, modem):
    """Shared whole-grid jnp driver for the fused demap cores."""
    n_rx, n_tx = y.shape[-1], h.shape[-1]
    nb = modem.bits_per_symbol // 2
    f32 = lambda v: v.astype(jnp.float32)
    yr = [f32(jnp.real(y[..., r])) for r in range(n_rx)]
    yi = [f32(jnp.imag(y[..., r])) for r in range(n_rx)]
    # h broadcasts over the symbol axis — never materialized per-symbol
    hr = [[f32(jnp.real(h[:, None, :, r, t])) for t in range(n_tx)]
          for r in range(n_rx)]
    hi = [[f32(jnp.imag(h[:, None, :, r, t])) for t in range(n_tx)]
          for r in range(n_rx)]
    xr, xi, nve, llr = core(
        yr, yi, hr, hi, noise_var, modem.levels, modem.norm, nb
    )
    shape = y.shape[:-1]
    x_hat = jnp.stack(
        [jnp.broadcast_to(xr[t] + 1j * xi[t], shape) for t in range(n_tx)],
        axis=-1,
    )
    nv_eff = jnp.stack(
        [jnp.broadcast_to(nve[t], shape) for t in range(n_tx)], axis=-1
    )
    llr_out = jnp.stack(
        [jnp.stack(
            [jnp.broadcast_to(b, shape) for b in llr[t]], axis=-1
        ) for t in range(n_tx)], axis=-2
    )
    return x_hat, nv_eff, llr_out


def mmse_detect_demap_jnp(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    h: jax.Array,  # (B, n_sc, n_rx, n_tx) complex (flat in time)
    noise_var: jax.Array,
    modem,  # repro.phy.ofdm.Modem (duck-typed: levels/norm/bits_per_symbol)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused math on whole grids; XLA fuses the unrolled element-wise chain.

    Returns (x_hat (B, n_sym, n_sc, n_tx), nv_eff, llr (..., n_tx, nb)).
    """
    return _demap_jnp(_detect_demap_core, y, h, noise_var, modem)


def sic_detect_demap_jnp(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    h: jax.Array,  # (B, n_sc, n_rx, n_tx) complex (flat in time)
    noise_var: jax.Array,
    modem,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused SIC math on whole grids (see :func:`_sic_core`); same return
    contract as :func:`mmse_detect_demap_jnp`."""
    return _demap_jnp(_sic_core, y, h, noise_var, modem)


# ---------------------------------------------------------------------------
# fused equalize -> demap: Pallas kernel
# ---------------------------------------------------------------------------

def _detect_demap_kernel(y_ref, h_ref, nv_ref, llr_ref, xh_ref, nve_ref, *,
                         n_rx: int, n_tx: int, n_sym: int,
                         levels: tuple, norm: float, nb: int,
                         core=_detect_demap_core):
    """Grid: (batch, sc_tiles).  Blocks: y (2*n_rx, 1, n_sym, bs),
    h (2*n_rx*n_tx, 1, 1, bs) — H broadcasts over symbols inside the tile,
    the per-symbol h_eff grid never exists.  ``core`` picks the fused math
    (:func:`_detect_demap_core` joint LMMSE or :func:`_sic_core` staged
    cancellation — same tile I/O either way)."""
    nv = nv_ref[0, 0]
    yr = [y_ref[r, 0] for r in range(n_rx)]  # (n_sym, bs)
    yi = [y_ref[n_rx + r, 0] for r in range(n_rx)]
    hr = [[h_ref[r * n_tx + t, 0] for t in range(n_tx)]
          for r in range(n_rx)]  # (1, bs)
    hi = [[h_ref[(n_rx + r) * n_tx + t, 0] for t in range(n_tx)]
          for r in range(n_rx)]
    xr, xi, nve, llr = core(yr, yi, hr, hi, nv, levels, norm, nb)
    bs = yr[0].shape[-1]
    for t in range(n_tx):
        xh_ref[t, 0] = jnp.broadcast_to(xr[t], (n_sym, bs))
        xh_ref[n_tx + t, 0] = jnp.broadcast_to(xi[t], (n_sym, bs))
        nve_ref[t, 0] = jnp.broadcast_to(nve[t], (n_sym, bs))
        for p in range(2 * nb):
            llr_ref[t * 2 * nb + p, 0] = jnp.broadcast_to(
                llr[t][p], (n_sym, bs)
            )


def _default_block_sc(n_sc: int) -> int:
    for bs in (512, 256, 128, 64):
        if n_sc % bs == 0 and bs <= n_sc:
            return bs
    return n_sc


def _demap_pallas(
    core,
    tune_op: str,
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    h: jax.Array,  # (B, n_sc, n_rx, n_tx) complex
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    interpret = resolve_interpret(interpret)
    b, n_sym, n_sc, n_rx = y.shape
    n_tx = h.shape[-1]
    nb = modem.bits_per_symbol // 2
    levels = tuple(float(v) for v in modem.levels)
    if block_sc is None:
        cached = tune.cached_choice(
            tune_op, (n_sym, n_sc, n_rx, n_tx, len(levels))
        )
        block_sc = (cached[0] if cached and n_sc % cached[0] == 0
                    else _default_block_sc(n_sc))
    bs = min(block_sc, n_sc)
    assert n_sc % bs == 0, f"n_sc={n_sc} not divisible by block_sc={bs}"

    # split-complex planar layout: leading dims index (component, rx[, tx]),
    # trailing (rows, subcarriers) are the tiled axes
    f32 = jnp.float32
    yp = jnp.stack([jnp.real(y), jnp.imag(y)], 0)  # (2, B, sym, sc, rx)
    yp = jnp.moveaxis(yp, -1, 1).reshape(2 * n_rx, b, n_sym, n_sc)
    hp = jnp.stack([jnp.real(h), jnp.imag(h)], 0)  # (2, B, sc, rx, tx)
    hp = jnp.transpose(hp, (0, 3, 4, 1, 2)).reshape(
        2 * n_rx * n_tx, b, 1, n_sc
    )
    nv2d = jnp.full((1, 1), noise_var, f32)

    kernel = functools.partial(
        _detect_demap_kernel, n_rx=n_rx, n_tx=n_tx, n_sym=n_sym,
        levels=levels, norm=float(modem.norm), nb=nb, core=core,
    )
    nbits = 2 * nb
    llr_p, xh_p, nve_p = pl.pallas_call(
        kernel,
        grid=(b, n_sc // bs),
        in_specs=[
            pl.BlockSpec((2 * n_rx, 1, n_sym, bs), lambda i, j: (0, i, 0, j)),
            pl.BlockSpec((2 * n_rx * n_tx, 1, 1, bs),
                         lambda i, j: (0, i, 0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((n_tx * nbits, 1, n_sym, bs),
                         lambda i, j: (0, i, 0, j)),
            pl.BlockSpec((2 * n_tx, 1, n_sym, bs), lambda i, j: (0, i, 0, j)),
            pl.BlockSpec((n_tx, 1, n_sym, bs), lambda i, j: (0, i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tx * nbits, b, n_sym, n_sc), f32),
            jax.ShapeDtypeStruct((2 * n_tx, b, n_sym, n_sc), f32),
            jax.ShapeDtypeStruct((n_tx, b, n_sym, n_sc), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=tune_op,
    )(yp.astype(f32), hp.astype(f32), nv2d)

    x_hat = jnp.moveaxis(xh_p[:n_tx] + 1j * xh_p[n_tx:], 0, -1)
    nv_eff = jnp.moveaxis(nve_p, 0, -1)
    llr = jnp.transpose(
        llr_p.reshape(n_tx, nbits, b, n_sym, n_sc), (2, 3, 4, 0, 1)
    )
    return x_hat, nv_eff, llr


def mmse_detect_demap_pallas(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    h: jax.Array,  # (B, n_sc, n_rx, n_tx) complex
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    return _demap_pallas(
        _detect_demap_core, "rx_detect_demap", y, h, noise_var, modem,
        block_sc=block_sc, interpret=interpret,
    )


def sic_detect_demap_pallas(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    h: jax.Array,  # (B, n_sc, n_rx, n_tx) complex
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused SIC equalize→demap as one Pallas pass: every cancellation
    stage's shrinking Gram/Gauss solve *and* the residual updates stay in
    the same VMEM tile (tuned separately from the joint-LMMSE kernel —
    the per-tile arithmetic is ~n_tx times heavier)."""
    return _demap_pallas(
        _sic_core, "rx_sic_demap", y, h, noise_var, modem,
        block_sc=block_sc, interpret=interpret,
    )


def mmse_detect_demap(
    y: jax.Array,
    h: jax.Array,
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    precision: Optional[str] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused MMSE equalize→demap; backend-dispatched (see module doc).

    ``precision="int8"|"fp8"`` emits LLRs rounded onto the fixed int8 grid
    of :mod:`repro.kernels.quant` (what the quantized decode stage and
    baseband silicon consume); the returned array stays fp32 so the rest
    of the chain is shape/dtype-stable.  Use
    :func:`mmse_detect_demap_int8` for the raw (int8 codes, scale) pair.
    """
    if _use_pallas(use_pallas):
        out = mmse_detect_demap_pallas(
            y, h, noise_var, modem, block_sc=block_sc, interpret=interpret
        )
    else:
        out = mmse_detect_demap_jnp(y, h, noise_var, modem)
    if precision is None or not quant.is_quantized(precision):
        return out
    x_hat, nv_eff, llr = out
    return x_hat, nv_eff, quant.fake_quant_llr(llr, precision)


def sic_detect_demap(
    y: jax.Array,
    h: jax.Array,
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    precision: Optional[str] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused SIC equalize→demap; backend-dispatched like
    :func:`mmse_detect_demap` (Pallas on TPU, one XLA-fused jnp function
    elsewhere), parity-gated against :func:`repro.kernels.ref.
    sic_detect_demap_ref`.  ``precision`` behaves as in
    :func:`mmse_detect_demap`."""
    if _use_pallas(use_pallas):
        out = sic_detect_demap_pallas(
            y, h, noise_var, modem, block_sc=block_sc, interpret=interpret
        )
    else:
        out = sic_detect_demap_jnp(y, h, noise_var, modem)
    if precision is None or not quant.is_quantized(precision):
        return out
    x_hat, nv_eff, llr = out
    return x_hat, nv_eff, quant.fake_quant_llr(llr, precision)


def mmse_detect_demap_int8(
    y: jax.Array,
    h: jax.Array,
    noise_var: jax.Array,
    modem,
    *,
    block_sc: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    llr_clip: float = quant.LLR_CLIP,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Quantized-LLR demap: (x_hat, nv_eff, llr_q int8, scale fp32).

    ``dequantize_llr(llr_q, scale)`` reproduces exactly what the
    ``precision="int8"`` path of :func:`mmse_detect_demap` feeds the
    decoder; the int8 codes are what a hardware demapper would DMA out
    (4x smaller than the fp32 LLR plane).
    """
    x_hat, nv_eff, llr = mmse_detect_demap(
        y, h, noise_var, modem, block_sc=block_sc, use_pallas=use_pallas,
        interpret=interpret,
    )
    llr_q, scale = quant.quantize_llr(llr, clip=llr_clip)
    return x_hat, nv_eff, llr_q, scale


# ---------------------------------------------------------------------------
# fused LS channel estimation
# ---------------------------------------------------------------------------

LS_MIN_BLOCK_SC = 256  # least subcarriers of one LS-CHE tile


def _interp_weights(n_sc: int, n_tx: int, pilot_stride: int,
                    seq: np.ndarray) -> tuple:
    """Clamped linear interpolation from each tx's DMRS comb, with the
    per-pilot divide folded in.  Returns pilot indices (n_tx, n_sc, 2)
    into the comb and complex weights (n_tx, n_sc, 2): subcarrier ``s``
    of tx ``t`` is ``sum_k w[t, s, k] * ybar[comb_t][idx[t, s, k]]``."""
    spacing = pilot_stride * n_tx
    assert n_sc % spacing == 0, (
        f"n_sc={n_sc} not a multiple of the comb spacing {spacing}"
    )
    n_p = n_sc // spacing
    seq = np.asarray(seq)
    s = np.arange(n_sc)
    idx = np.zeros((n_tx, n_sc, 2), np.int64)
    w = np.zeros((n_tx, n_sc, 2), np.complex64)
    for t in range(n_tx):
        p_idx = t * pilot_stride + spacing * np.arange(n_p)
        i = np.clip((s - t * pilot_stride) // spacing, 0, max(n_p - 2, 0))
        j = np.minimum(i + 1, n_p - 1)
        # f = 0 left of the first pilot, 1 right of the last: clamped ends
        f = np.clip((s - p_idx[i]) / spacing, 0.0, 1.0) if n_p > 1 else 0.0
        idx[t, :, 0], idx[t, :, 1] = i, j
        w[t, :, 0] = (1.0 - f) * np.conj(seq[p_idx[i]])
        w[t, :, 1] = f * np.conj(seq[p_idx[j]])
    return idx, w


@functools.partial(jax.tree_util.register_dataclass, data_fields=["band"],
                   meta_fields=["n_sc", "pilot_stride", "block_sc"])
@dataclasses.dataclass(frozen=True)
class LsInterpOperator:
    """The LS interpolation operator, cut into subcarrier tiles.

    Tile ``j`` holds subcarriers ``[j * block_sc, (j + 1) * block_sc)``
    and needs only the ``width`` comb pilots ``j * P - 1 ...`` of each tx
    (``P = block_sc / spacing`` pilots lie inside the tile, one more on
    each side).  ``band[t, w, s]`` is the weight of tile ``s // block_sc``'s
    ``w``-th window pilot for subcarrier ``s``; the subcarriers past
    ``n_sc`` in the last tile have none.  Built by
    :func:`make_ls_interp_operator`.
    """
    band: jax.Array  # (n_tx, width, n_tiles * block_sc) complex64
    n_sc: int
    pilot_stride: int
    block_sc: int

    @property
    def n_tx(self) -> int:
        return self.band.shape[0]

    @property
    def width(self) -> int:
        return self.band.shape[1]

    @property
    def spacing(self) -> int:
        return self.pilot_stride * self.n_tx

    @property
    def n_p(self) -> int:
        return self.n_sc // self.spacing

    @property
    def n_tiles(self) -> int:
        return self.band.shape[2] // self.block_sc

    def dense(self, seq: np.ndarray) -> jax.Array:
        """(n_tx, n_p, n_sc) dense operator of :func:`ls_che_jnp`, built
        from the interpolation weights and not from ``band``."""
        idx, w = _interp_weights(self.n_sc, self.n_tx, self.pilot_stride,
                                 seq)
        op = np.zeros((self.n_tx, self.n_p, self.n_sc), np.complex64)
        t, s = np.meshgrid(np.arange(self.n_tx), np.arange(self.n_sc),
                           indexing="ij")
        for k in range(2):
            np.add.at(op, (t, idx[..., k], s), w[..., k])
        return jnp.asarray(op)


def ls_block_sc(n_tx: int, pilot_stride: int) -> int:
    """Subcarrier tile of the LS-CHE kernel: the least multiple of the
    128-lane quantum and of the comb spacing that is at least
    :data:`LS_MIN_BLOCK_SC`."""
    q = np.lcm(_LANE, pilot_stride * n_tx)
    return int(_round_up(LS_MIN_BLOCK_SC, q))


def make_ls_interp_operator(n_sc: int, n_tx: int, pilot_stride: int,
                            seq: np.ndarray) -> LsInterpOperator:
    """Fold the per-pilot divide and the clamped linear frequency
    interpolation into one banded operator, tiled over subcarriers:

        H_ls[s, t] = sum_w window_j(ybar[comb_t])[w] * band[t, w, s]

    where ``ybar`` is the pilot-symbol average of the received grid,
    ``comb_t`` the stride-``pilot_stride * n_tx`` DMRS comb of tx ``t``
    and ``window_j`` the ``width`` pilots tile ``j = s // block_sc``
    reads.  Pilot sequences are unit power, so dividing by ``seq`` is
    multiplying by its conjugate, which folds into the operator.

    Accepts any ``n_sc`` that the comb spacing divides (a multiple of 128
    or not: the last tile is padded).  Tiles are :func:`ls_block_sc`
    wide, a multiple of 128 and of the spacing; the operator holds
    ``n_tx * width * block_sc`` weights a tile, with
    ``width = block_sc / spacing + 2`` rounded up to 8, whatever ``n_sc``,
    and a kernel grid step needs :func:`ls_che_vmem_bytes` of VMEM (0.95
    MB at batch 8 x 4 rx, one tx, 256-subcarrier tiles).
    """
    spacing = pilot_stride * n_tx
    idx, w = _interp_weights(n_sc, n_tx, pilot_stride, seq)
    bs = ls_block_sc(n_tx, pilot_stride)
    width = _round_up(bs // spacing + 2, _SUBLANE)
    n_pad = _round_up(n_sc, bs)
    s = np.arange(n_sc)
    # window position: tile j's window starts at comb pilot j * P - 1
    pos = idx - ((s // bs) * (bs // spacing) - 1)[None, :, None]
    assert pos.min() >= 0 and pos.max() < width
    band = np.zeros((n_tx, width, n_pad), np.complex64)
    t, ss = np.meshgrid(np.arange(n_tx), s, indexing="ij")
    for k in range(2):
        np.add.at(band, (t, pos[..., k], ss), w[..., k])
    return LsInterpOperator(jnp.asarray(band), n_sc, pilot_stride, bs)


def _comb_extract(y: jax.Array, pilot_symbols: tuple, pilot_stride: int,
                  n_tx: int) -> jax.Array:
    """(B, n_psym, n_tx, n_p, n_rx) static strided gather of the DMRS REs."""
    spacing = pilot_stride * n_tx
    yp = y[:, jnp.asarray(pilot_symbols)]  # (B, n_psym, n_sc, n_rx)
    return jnp.stack(
        [yp[:, :, t * pilot_stride::spacing, :] for t in range(n_tx)], axis=2
    )


def _pilot_windows(x: jax.Array, op: LsInterpOperator) -> jax.Array:
    """(..., n_p) comb pilots -> (..., n_tiles, width): tile ``j`` gets
    pilots ``j * P - 1 ... j * P + width - 2``, zero past either end."""
    per = op.block_sc // op.spacing
    total = (op.n_tiles - 1) * per + op.width
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                 + [(1, total - 1 - x.shape[-1])])
    return jnp.stack([xp[..., j * per : j * per + op.width]
                      for j in range(op.n_tiles)], axis=-2)


def ls_che_jnp(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    pilot_symbols: tuple,
    pilot_stride: int,
    op: jax.Array,  # (n_tx, n_p, n_sc), LsInterpOperator.dense
) -> jax.Array:
    """LS CHE as one dense GEMM against the whole operator: the plain
    form the tiled routes are checked against."""
    n_tx = op.shape[0]
    comb = jnp.mean(
        _comb_extract(y, pilot_symbols, pilot_stride, n_tx), axis=1
    )  # (B, n_tx, n_p, n_rx)
    return jnp.einsum("btpr,tps->bsrt", comb, op)


def ls_che_tiled_jnp(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) complex
    pilot_symbols: tuple,
    op: LsInterpOperator,
) -> jax.Array:
    """The kernel's tiled math on whole grids (the off-TPU route)."""
    comb = jnp.mean(
        _comb_extract(y, pilot_symbols, op.pilot_stride, op.n_tx), axis=1
    )  # (B, n_tx, n_p, n_rx)
    win = _pilot_windows(jnp.moveaxis(comb, 2, -1), op)  # (B,t,r,j,w)
    band = op.band.reshape(op.n_tx, op.width, op.n_tiles, op.block_sc)
    h = jnp.einsum("btrjw,twjs->bjsrt", win, band)
    return h.reshape(h.shape[0], -1, *h.shape[3:])[:, : op.n_sc]


def _ls_che_kernel(yc_ref, op_ref, o_ref, *, n_psym: int, n_tx: int):
    """Grid: (row_tiles, sc_tiles).  Pilot-symbol average of the tile's
    pilot window + split-complex banded interp GEMM per tx; the per-pilot
    LS estimates never leave VMEM."""
    inv = 1.0 / n_psym
    for t in range(n_tx):
        er = sum(yc_ref[0, p * n_tx + t] for p in range(n_psym)) * inv
        ei = sum(yc_ref[0, (n_psym + p) * n_tx + t]
                 for p in range(n_psym)) * inv  # (bm, width)
        mr, mi = op_ref[t], op_ref[n_tx + t]  # (width, bs)
        dot = lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
        o_ref[t] = dot(er, mr) - dot(ei, mi)
        o_ref[n_tx + t] = dot(er, mi) + dot(ei, mr)


def ls_che_vmem_bytes(n_tx: int, n_psym: int, block_rows: int,
                      block_sc: int, width: int) -> int:
    """VMEM of one ``rx_ls_che`` grid step, double-buffered, f32 blocks
    padded to Mosaic's (8, 128) tile: the pilot window
    ``(2 * n_psym * n_tx, block_rows, width)``, the operator tile
    ``(2 * n_tx, width, block_sc)`` and the output tile
    ``(2 * n_tx, block_rows, block_sc)``.  None of them holds ``n_sc``."""
    rows = _round_up(block_rows, _SUBLANE)
    pilots = 2 * n_psym * n_tx * rows * _round_up(width, _LANE)
    band = 2 * n_tx * _round_up(width, _SUBLANE) * block_sc
    out = 2 * n_tx * rows * block_sc
    return 2 * 4 * (pilots + band + out)


def _ls_block_rows(rows: int, n_sc: int, n_rx: int, n_tx: int,
                   n_p: int) -> int:
    """Row tile: a tuned choice, else the largest of 64/32/16/8 that
    divides ``rows``, else all rows (Mosaic's 8-row quantum)."""
    cached = tune.cached_choice("rx_ls_che", (n_sc, n_rx, n_tx, n_p))
    if cached and rows % cached[0] == 0:
        return cached[0]
    return next((c for c in (64, 32, 16, 8) if rows % c == 0), rows)


def ls_che_pallas(
    y: jax.Array,
    pilot_symbols: tuple,
    pilot_stride: int,
    op: LsInterpOperator,
    *,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused LS CHE as one Pallas pass over (row tile, subcarrier tile).

    Each grid step reads one row tile's pilot window and one operator
    tile and writes one ``h_ls`` tile, so its VMEM
    (:func:`ls_che_vmem_bytes`) is set by the tile shapes and not by
    ``n_sc``; the last subcarrier tile is padded and the padding sliced
    off.  Returns (B, n_sc, n_rx, n_tx)."""
    assert pilot_stride == op.pilot_stride
    interpret = resolve_interpret(interpret)
    b, n_sym, n_sc, n_rx = y.shape
    n_tx, n_psym = op.n_tx, len(pilot_symbols)
    width, bs = op.width, op.block_sc
    rows = b * n_rx
    bm = min(block_rows or _ls_block_rows(rows, n_sc, n_rx, n_tx, op.n_p),
             rows)
    assert rows % bm == 0

    f32 = jnp.float32
    comb = _comb_extract(y, pilot_symbols, pilot_stride, n_tx)
    # (2, n_psym, n_tx, rows, n_p): component-major planar layout
    yc = jnp.stack([jnp.real(comb), jnp.imag(comb)], 0)
    yc = jnp.transpose(yc, (0, 2, 3, 1, 5, 4)).reshape(
        2 * n_psym * n_tx, rows, op.n_p
    )
    win = jnp.moveaxis(_pilot_windows(yc, op), 2, 0)  # (tiles, C, rows, w)
    opp = jnp.concatenate([jnp.real(op.band), jnp.imag(op.band)], 0)

    kernel = functools.partial(_ls_che_kernel, n_psym=n_psym, n_tx=n_tx)
    out = pl.pallas_call(
        kernel,
        grid=(rows // bm, op.n_tiles),
        in_specs=[
            pl.BlockSpec((1, 2 * n_psym * n_tx, bm, width),
                         lambda i, j: (j, 0, i, 0)),
            pl.BlockSpec((2 * n_tx, width, bs), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((2 * n_tx, bm, bs), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((2 * n_tx, rows, op.n_tiles * bs),
                                       f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="rx_ls_che",
    )(win.astype(f32), opp.astype(f32))

    out = out[..., :n_sc]
    h = (out[:n_tx] + 1j * out[n_tx:]).reshape(n_tx, b, n_rx, n_sc)
    return jnp.transpose(h, (1, 3, 2, 0))  # (B, n_sc, n_rx, n_tx)


def ls_che(
    y: jax.Array,
    pilot_symbols: tuple,
    pilot_stride: int,
    op: LsInterpOperator,
    *,
    block_rows: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused LS CHE (comb extract → divide → interp) against the tiled
    operator; backend-dispatched (Pallas on TPU, the same tiled math as
    jnp elsewhere)."""
    if _use_pallas(use_pallas):
        return ls_che_pallas(
            y, pilot_symbols, pilot_stride, op,
            block_rows=block_rows, interpret=interpret,
        )
    return ls_che_tiled_jnp(y, pilot_symbols, op)
