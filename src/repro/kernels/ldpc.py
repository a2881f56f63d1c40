"""Batched layered normalized-min-sum LDPC decoder (paper §II coded PHY).

Channel decoding is the third first-class baseband kernel next to CHE and
detection: the TTI budget covers CRC + LDPC decode, and the decoder's
inner loop is exactly the memory-residency story the paper tells — the
posterior LLR state must stay in L1 across *all* iterations, because every
layer reads and rewrites it.

Layout and schedule
-------------------
The code is quasi-cyclic (:class:`repro.phy.coding.CodeConfig`): a base
graph lifted by circulant size ``z``.  Within one block row (a *layer*)
the ``z`` lifted checks touch disjoint variable bits, so a layer update is
pure tensor work:

* state ``v`` is laid out ``(n_b, z, batch_tile)`` — block column, lifted
  row, codeword.  Codewords ride the 128-wide lane axis (each lane decodes
  an independent codeword; a batch pads to whole 128-lane tiles),
  circulant rotations are sublane rolls along ``z`` (``pltpu.roll`` in
  the kernel, ``jnp.roll`` elsewhere), and the check-node min /
  second-min / sign-product reduce over the (static, unrolled) edge
  axis.
* one grid step owns a batch tile; the whole iteration loop runs *inside*
  the kernel, so ``v`` and the per-layer check messages are VMEM-resident
  across iterations — HBM sees one LLR read and one posterior write per
  codeword, not one per iteration.
* iterations early-exit on the parity syndrome: converged codewords freeze
  (their state stops updating, exactly like stopping), and the loop ends
  when the whole tile is converged.  The per-codeword iteration count is
  an output — serving reports it as decode effort.

As with the other receiver kernels, the arithmetic lives in a shared core
(`_decode_core`) consumed by the Pallas kernel on TPU and by a plain-jnp
path elsewhere (interpret-mode Pallas would be orders of magnitude slower
than the XLA fusion it replaces).  ``kernels/ref.py`` carries an
independent per-row numpy oracle.  Batch-tile shapes resolve through the
:mod:`repro.kernels.tune` cache before the static default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant, tune
from repro.kernels.runtime import resolve_interpret

DEFAULT_MAX_ITERS = 12
DEFAULT_ALPHA = 0.8  # normalized-min-sum damping


def _use_pallas(use_pallas: Optional[bool]) -> bool:
    """None -> Pallas only where it compiles to Mosaic (TPU)."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


LANE = 128  # codewords per vreg lane row: the kernel's batch-tile quantum


# ---------------------------------------------------------------------------
# circulant rotations: static shifts, identity skipped
# ---------------------------------------------------------------------------
#
# Both rotate a (z, lanes) block along the sublane axis with jnp.roll's
# convention (``pltpu.roll`` is defined as jnp.roll off the chip, so
# interpret mode runs the kernel's own rotation).  A shift of 0 mod z is
# the identity and is skipped: jnp.roll would emit a zero-length slice,
# which Mosaic refuses.

def _roll_jnp(x: jax.Array, shift: int) -> jax.Array:
    shift %= x.shape[0]
    return x if shift == 0 else jnp.roll(x, shift, axis=0)


def _roll_tpu(x: jax.Array, shift: int) -> jax.Array:
    shift %= x.shape[0]
    return x if shift == 0 else pltpu.roll(x, shift, 0)


# ---------------------------------------------------------------------------
# shared layered min-sum core (standard convention: v = log P(0)/P(1))
# ---------------------------------------------------------------------------
#
# The body works on a list of n_b blocks of shape (z, lanes), one per
# block column, so every rotation and write-back is a whole-block op with
# a static shift.  A layer's E edges stack to (E, z, lanes) for the check
# update, whose reductions over the edge axis avoid what Mosaic cannot
# lower (integer argmin, reduce_prod).

def _unstack(x: jax.Array) -> list:
    return [x[i] for i in range(x.shape[0])]


def _syndrome_ok(v: list, layers: tuple, roll) -> jax.Array:
    """n_b blocks (z, L) -> (1, L) bool: all parity checks hold."""
    hard = [(b < 0).astype(jnp.int32) for b in v]
    bad = None
    for edges in layers:
        p = roll(hard[edges[0][0]], -edges[0][1])
        for c, s in edges[1:]:
            p = p ^ roll(hard[c], -s)
        bad = p if bad is None else bad | p
    return jnp.max(bad.astype(jnp.float32), axis=0, keepdims=True) == 0.0


def _check_update(t: jax.Array, alpha: float, quantized: bool) -> jax.Array:
    """Damped min-sum check messages for one layer, t (E, z, L).

    Each edge gets the min magnitude over the *other* edges (min1, or
    min2 on the first edge attaining min1, as an argmin would pick) and
    the sign product of the other edges.  fp32: ``alpha * mag``.  int8:
    the fixed-point damping ``(mag * round(alpha*256)) >> 8``, saturated
    onto the int8 grid.  Every reduction over the edge axis is a float32
    min or sum (integer magnitudes and sign counts are exact in float32),
    the one reduction kind Mosaic lowers for every dtype used here.
    """
    at = jnp.abs(t)
    atf = at.astype(jnp.float32)
    m1 = jnp.min(atf, axis=0, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0).astype(jnp.float32)
    first = jnp.min(jnp.where(atf == m1, idx, float(t.shape[0])), axis=0,
                    keepdims=True)
    is_min = idx == first
    m2 = jnp.min(jnp.where(is_min, jnp.inf, atf), axis=0, keepdims=True)
    mag = jnp.where(is_min, m2, m1)
    neg = t < 0
    n_neg = jnp.sum(jnp.where(neg, 1.0, 0.0), axis=0, keepdims=True)
    odd = n_neg - 2.0 * jnp.floor(n_neg * 0.5) > 0.5
    flip = neg != odd  # sign of the product over the *other* edges
    if quantized:
        q = quant.scale_q8(mag.astype(jnp.int32), alpha)
        return quant.sat8(jnp.where(flip, -q, q))
    return jnp.where(flip, -alpha, alpha) * mag


def _layered_iteration(v: list, c2v: tuple, layers: tuple, alpha: float,
                       roll, quantized: bool):
    """One full sweep over the layers.

    Per layer: form variable-to-check messages ``t`` (posterior minus the
    layer's previous check message), refresh the check messages
    (:func:`_check_update`), and write the new posterior back through the
    inverse rotations.  Layers see each other's updates within the sweep
    — that is what makes layered decoding converge in roughly half the
    iterations of flooding.  In the int8 variant check messages live on
    the int8 grid and the posterior saturates at the 12-bit ``_SAT_V``
    (both carried in int32 lanes — the *values* are narrow).
    """
    v = list(v)
    new_c2v = []
    for li, edges in enumerate(layers):
        t = jnp.stack([roll(v[c], -s) for c, s in edges]) - c2v[li]
        upd = _check_update(t, alpha, quantized)
        vn = t + upd
        if quantized:
            vn = jnp.clip(vn, -_SAT_V, _SAT_V)
        for e, (c, s) in enumerate(edges):
            v[c] = roll(vn[e], s)
        new_c2v.append(upd)
    return v, tuple(new_c2v)


def _decode_core(v0: list, layers: tuple, max_iters: int, alpha: float,
                 roll, quantized: bool = False):
    """Iterate to convergence.  n_b blocks (z, L) -> (posterior (n_b, z,
    L), iters (1, L) int32).

    Convergence is per lane: a converged codeword's state and messages
    freeze (identical numerics to stopping), and the while loop exits as
    soon as every lane is converged — the early-exit path that makes
    high-SNR traffic cheap.
    """
    # the loop carries stacked arrays (few, large carry buffers compile
    # far faster than one buffer per block and edge); the body works on
    # the unstacked blocks
    c2v0 = tuple(
        jnp.zeros((len(edges),) + v0[0].shape, v0[0].dtype)
        for edges in layers
    )
    # ``done`` rides the loop as int32 0/1: Mosaic cannot carry a
    # boolean vector through a loop
    done0 = _syndrome_ok(v0, layers, roll).astype(jnp.int32)
    iters0 = jnp.zeros(done0.shape, jnp.int32)

    def cond(carry):
        it, _, _, done, _ = carry
        pending = jnp.min(done.astype(jnp.float32)) == 0.0
        return jnp.logical_and(it < max_iters, pending)

    def body(carry):
        it, v, c2v, done, iters = carry
        keep = done > 0
        vn, c2vn = _layered_iteration(_unstack(v), c2v, layers, alpha,
                                      roll, quantized)
        v = jnp.where(keep, v, jnp.stack(vn))
        c2v = tuple(jnp.where(keep, a, b) for a, b in zip(c2v, c2vn))
        iters = iters + 1 - done
        ok = _syndrome_ok(_unstack(v), layers, roll).astype(jnp.int32)
        return it + 1, v, c2v, jnp.maximum(done, ok), iters

    _, v, _, _, iters = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.stack(v0), c2v0, done0, iters0)
    )
    return v, iters


# ---------------------------------------------------------------------------
# int8 LLR-state variant (saturating min/sum — what baseband silicon ships)
# ---------------------------------------------------------------------------

# Posterior accumulator saturation: check messages stay on the int8 grid,
# but the variable-node state gets 12-bit headroom (a standard min-sum
# datapath split).  At the registered operating points the channel LLRs sit
# near the int8 clip, so an int8 accumulator saturates on the *first*
# extrinsic add and the decoder loses ~2 dB; four extra accumulator bits
# recover the fp32 waterfall to within the 0.5 dB parity gate.
_SAT_V = 2047


def _quant_step(precision) -> Optional[float]:
    """LLR units per int8 code for a quantized policy, else None (fp32).

    int8/fp8 both select the saturating integer state (LLR state is
    integer in silicon for both 1-byte policies).  Min-sum is scale-
    equivariant, so one scalar step round-trips the whole decode.
    """
    if precision is None or not quant.is_quantized(precision):
        return None
    return float(quant.llr_scale())


def _to_lanes(llr: jax.Array, n_b: int, z: int,
              step: Optional[float] = None) -> jax.Array:
    """(B, n_b*z) repo-convention LLRs -> (n_b, z, B) internal state.

    The repo's demappers emit llr = log P(1)/P(0); min-sum runs in the
    log P(0)/P(1) convention, so the boundary negates.  With a quantized
    ``step`` the state is the int32-carried int8 grid.
    """
    b = llr.shape[0]
    v = -jnp.moveaxis(llr.reshape(b, n_b, z).astype(jnp.float32), 0, -1)
    if step is None:
        return v
    return jnp.clip(jnp.round(v / step), -127, 127).astype(jnp.int32)


def _from_lanes(v: jax.Array, step: Optional[float] = None) -> jax.Array:
    """(n_b, z, B) internal posterior -> (B, n_b*z) repo-convention."""
    if step is not None:
        v = v.astype(jnp.float32) * step
    n_b, z, b = v.shape
    return -jnp.moveaxis(v, -1, 0).reshape(b, n_b * z)


# ---------------------------------------------------------------------------
# jnp path (off-TPU fast route)
# ---------------------------------------------------------------------------

def ldpc_decode_jnp(llr: jax.Array, code, *,
                    max_iters: int = DEFAULT_MAX_ITERS,
                    alpha: float = DEFAULT_ALPHA,
                    precision: Optional[str] = None):
    """llr (B, n_mother) -> (posterior LLRs (B, n_mother), iters (B,))."""
    step = _quant_step(precision)
    v0 = _to_lanes(llr, code.n_b, code.z, step)
    v, iters = _decode_core(
        list(v0), code.layers(), max_iters, alpha, _roll_jnp,
        quantized=step is not None,
    )
    return _from_lanes(v, step), iters[0]


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _ldpc_kernel(v_ref, out_ref, it_ref, *, layers: tuple, max_iters: int,
                 alpha: float, quantized: bool):
    """Grid: (batch_tiles,).  The whole iteration loop runs in-kernel, so
    the (n_b, z, bt) state and the per-layer check messages never leave
    VMEM between iterations."""
    v0 = [v_ref[c] for c in range(v_ref.shape[0])]
    v, iters = _decode_core(v0, layers, max_iters, alpha, _roll_tpu,
                            quantized)
    out_ref[...] = v
    it_ref[...] = iters


def ldpc_decode_pallas(llr: jax.Array, code, *,
                       max_iters: int = DEFAULT_MAX_ITERS,
                       alpha: float = DEFAULT_ALPHA,
                       block_b: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       precision: Optional[str] = None):
    """Pallas decode.  Codewords ride the lane axis in tiles of
    ``block_b`` (default: a multiple of :data:`LANE`, from the tune cache
    or ``LANE``); the batch is zero-padded to a whole number of tiles.
    Zero LLRs satisfy every parity check, so pad lanes converge before
    the first iteration and never hold a tile's loop open."""
    interpret = resolve_interpret(interpret)
    b = llr.shape[0]
    n_b, z = code.n_b, code.z
    if block_b is None:
        cached = tune.cached_choice(
            "ldpc_decode", (code.k_b, code.m_b, z, max_iters)
        )
        block_b = (cached[0] if cached and cached[0] % LANE == 0
                   else LANE)
    bt = int(block_b)
    bp = -(-b // bt) * bt
    step = _quant_step(precision)
    v0 = _to_lanes(llr, n_b, z, step)
    if bp != b:
        v0 = jnp.pad(v0, ((0, 0), (0, 0), (0, bp - b)))

    kernel = functools.partial(
        _ldpc_kernel, layers=code.layers(), max_iters=max_iters,
        alpha=float(alpha), quantized=step is not None,
    )
    v, iters = pl.pallas_call(
        kernel,
        grid=(bp // bt,),
        in_specs=[pl.BlockSpec((n_b, z, bt), lambda i: (0, 0, i))],
        out_specs=[
            pl.BlockSpec((n_b, z, bt), lambda i: (0, 0, i)),
            pl.BlockSpec((1, bt), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_b, z, bp), v0.dtype),
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="ldpc_decode",
    )(v0)
    return _from_lanes(v[..., :b], step), iters[0, :b]


def ldpc_decode(llr: jax.Array, code, *,
                max_iters: int = DEFAULT_MAX_ITERS,
                alpha: float = DEFAULT_ALPHA,
                block_b: Optional[int] = None,
                use_pallas: Optional[bool] = None,
                interpret: Optional[bool] = None,
                precision: Optional[str] = None):
    """Layered normalized-min-sum decode; backend-dispatched (module doc).

    ``llr`` (B, n_mother) in the repo's log P(1)/P(0) convention (zero =
    punctured/erased).  Returns (posterior LLRs, per-codeword iteration
    counts); hard decisions are ``posterior > 0``.

    ``precision="int8"|"fp8"`` runs the saturating int8 LLR-state variant
    (channel LLRs quantized onto the :mod:`repro.kernels.quant` grid,
    integer min/sign/damping, saturating adds); posterior LLRs come back
    dequantized to fp32 so callers are dtype-stable.
    """
    if _use_pallas(use_pallas):
        return ldpc_decode_pallas(
            llr, code, max_iters=max_iters, alpha=alpha, block_b=block_b,
            interpret=interpret, precision=precision,
        )
    return ldpc_decode_jnp(llr, code, max_iters=max_iters, alpha=alpha,
                           precision=precision)
