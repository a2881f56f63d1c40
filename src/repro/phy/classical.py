"""Classical wireless signal processing (paper §V-B PE workloads):
CFFT, LS / MMSE channel estimation, MIMO-MMSE detection.

These are the paper's "PEs are still precious" kernels — elementwise / small
linear-algebra work that does not map to the tensor engines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cfft(x: jax.Array, axis: int = -1) -> jax.Array:
    """Complex FFT (the PE CFFT kernel; paper Fig. 8)."""
    return jnp.fft.fft(x, axis=axis)


def cfft_auto(x: jax.Array, axis: int = -1,
              prefer_butterfly: bool = False) -> jax.Array:
    """CFFT for any transform length — no failing assert, no padding.

    The default is the native ``jnp.fft.fft`` (the fast path on every
    backend).  ``prefer_butterfly=True`` routes radix-2-power lengths
    through the paper-faithful :func:`cfft_radix2` PE formulation instead,
    still falling back to ``jnp.fft.fft`` for any other length.
    """
    n = x.shape[axis]
    if prefer_butterfly and n > 1 and n & (n - 1) == 0:
        if axis in (-1, x.ndim - 1):
            return cfft_radix2(x)
        return jnp.moveaxis(cfft_radix2(jnp.moveaxis(x, axis, -1)), -1, axis)
    return jnp.fft.fft(x, axis=axis)


def cfft_radix2(x: jax.Array) -> jax.Array:
    """Iterative radix-2 DIT FFT over the last axis (power-of-two length).

    The explicit butterfly formulation that runs on the paper's PEs —
    validated against jnp.fft in tests.
    """
    n = x.shape[-1]
    assert n & (n - 1) == 0, "radix-2 needs power-of-two length"
    # bit reversal permutation
    idx = jnp.arange(n)
    bits = n.bit_length() - 1
    rev = jnp.zeros_like(idx)
    for b in range(bits):
        rev = rev | (((idx >> b) & 1) << (bits - 1 - b))
    y = x[..., rev].astype(jnp.complex64)
    size = 2
    while size <= n:
        half = size // 2
        tw = jnp.exp(-2j * jnp.pi * jnp.arange(half) / size)
        y = y.reshape(*y.shape[:-1], n // size, size)
        even = y[..., :half]
        odd = y[..., half:] * tw
        y = jnp.concatenate([even + odd, even - odd], axis=-1)
        y = y.reshape(*y.shape[:-2], n)
        size *= 2
    return y


def ls_channel_estimate(
    y: jax.Array,  # (B, n_sym, n_sc) received grid
    pilots: jax.Array,  # (n_sc,) known pilot symbols
    pilot_mask: jax.Array,  # (n_sym, n_sc) bool
    pilot_stride: int = 4,  # static pilot subcarrier spacing
) -> jax.Array:
    """LS estimate at pilots + linear interpolation across subcarriers.

    Returns H_hat (B, n_sc) (channel flat in time within the slot).
    """
    # average LS estimates over pilot symbols
    est = y / pilots[None, None, :]  # (B, n_sym, n_sc)
    w = pilot_mask.astype(jnp.float32)[None]
    h_p = jnp.sum(est * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1e-9)
    # interpolate from the (static) pilot comb to all subcarriers
    n_sc = y.shape[-1]
    pos = jnp.arange(n_sc, dtype=jnp.float32)
    p_idx = jnp.arange(0, n_sc, pilot_stride)
    xp = pos[p_idx]
    fp = h_p[:, p_idx]  # (B, n_p)
    re = jax.vmap(lambda f: jnp.interp(pos, xp, f))(jnp.real(fp))
    im = jax.vmap(lambda f: jnp.interp(pos, xp, f))(jnp.imag(fp))
    return re + 1j * im


def wiener_filter(
    n_sc: int,
    corr_len: float,
    noise_var: jax.Array,
    pos: jax.Array | None = None,
) -> jax.Array:
    """The Wiener smoother W = (R + sigma^2 I)^-1 R of an exponential
    frequency correlation R = exp(-|i - j| / corr_len), in the orientation
    the apply consumes: H_mmse[s] = sum_k W[s, k] H_ls[k].  R commutes with
    (R + sigma^2 I)^-1, so this is R (R + sigma^2 I)^-1 too.

    ``pos`` is the subcarrier index as float32.  Left out, it is an iota,
    and R a function of ``n_sc`` alone that XLA is free to fold into an
    n_sc x n_sc constant of the calling program; given as a runtime
    argument, R can only be computed on the device (the serving filter
    cache builds W this way, :mod:`repro.serve.exec_registry`).
    """
    if pos is None:
        pos = jnp.arange(n_sc, dtype=jnp.float32)
    d = jnp.abs(pos[:, None] - pos[None, :])
    r = jnp.exp(-d / corr_len).astype(jnp.complex64)
    eye = (d == 0).astype(jnp.complex64)
    return jnp.linalg.solve(r + noise_var * eye, r)  # (n_sc, n_sc)


def mmse_channel_estimate(
    h_ls: jax.Array,  # (B, n_sc) LS estimate
    noise_var: jax.Array,
    corr_len: float = 16.0,
) -> jax.Array:
    """Wiener smoothing of the LS estimate with an exponential frequency
    correlation model: H_mmse = R (R + sigma^2 I)^-1 H_ls.

    The filter is built here, in the caller's graph
    (:func:`wiener_filter`); the served step instead reads one the
    serving filter cache built once per noise variance
    (:func:`repro.phy.link.mmse_che_stage`)."""
    w = wiener_filter(h_ls.shape[-1], corr_len, noise_var)
    return wiener_apply(w, h_ls)


def wiener_apply(w: jax.Array, h_ls: jax.Array) -> jax.Array:
    """Apply a :func:`wiener_filter` ``w`` (n_sc, n_sc) to ``h_ls``
    (B, n_sc)."""
    return jnp.einsum("sk,bk->bs", w, h_ls)


def _regularized_gram_rhs(
    y: jax.Array,  # (B, n_sc, n_rx)
    h: jax.Array,  # (B, n_sc, n_rx, n_tx)
    noise_var: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared MMSE front end: (gram H^H H, A = gram + s2 I, rhs H^H y)."""
    n_tx = h.shape[-1]
    hh = jnp.conj(jnp.swapaxes(h, -1, -2))  # (B, n_sc, n_tx, n_rx)
    gram = jnp.einsum("bstr,bsru->bstu", hh, h)
    a = gram + noise_var * jnp.eye(n_tx, dtype=h.dtype)
    rhs = jnp.einsum("bstr,bsr->bst", hh, y)
    return gram, a, rhs


def mimo_mmse_detect(
    y: jax.Array,  # (B, n_sc, n_rx)
    h: jax.Array,  # (B, n_sc, n_rx, n_tx)
    noise_var: jax.Array,
) -> jax.Array:
    """Per-subcarrier MMSE equalizer: x = (H^H H + s2 I)^-1 H^H y."""
    _, a, rhs = _regularized_gram_rhs(y, h, noise_var)
    return jnp.linalg.solve(a, rhs[..., None])[..., 0]  # (B, n_sc, n_tx)


def mimo_mmse_detect_ext(
    y: jax.Array,  # (B, n_sc, n_rx)
    h: jax.Array,  # (B, n_sc, n_rx, n_tx)
    noise_var: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Unbiased MMSE detection with per-stream post-equalization noise.

    The raw MMSE output is biased by mu_t = [ (H^H H + s2 I)^-1 H^H H ]_tt;
    dividing by mu_t restores unit gain, and the residual noise variance of
    the unbiased estimate is (1 - mu_t) / mu_t (unit-power symbols) — the
    quantity a multi-level demapper needs for correctly scaled LLRs.

    Returns (x_hat_unbiased (B, n_sc, n_tx), nv_eff (B, n_sc, n_tx)).
    """
    gram, a, rhs = _regularized_gram_rhs(y, h, noise_var)
    # one factorization for both the filter output and the bias diagonal
    sol = jnp.linalg.solve(a, jnp.concatenate([rhs[..., None], gram], -1))
    x_mmse = sol[..., 0]
    mu = jnp.clip(
        jnp.real(jnp.diagonal(sol[..., 1:], axis1=-2, axis2=-1)),
        1e-6, 1.0 - 1e-6,
    )  # (B, n_sc, n_tx)
    return x_mmse / mu, (1.0 - mu) / mu


def mimo_sic_detect_ext(
    y: jax.Array,  # (B, n_sc, n_rx)
    h: jax.Array,  # (B, n_sc, n_rx, n_tx)
    noise_var: jax.Array,
    modem,  # repro.phy.ofdm.Modem
) -> tuple[jax.Array, jax.Array]:
    """Successive interference cancellation on top of the unbiased MMSE
    detector: detect a stream, hard-decide it on the modem's grid,
    subtract its reconstructed contribution, and re-solve the shrunken
    system for the remaining streams.

    Streams are cancelled in index order — the repo's MU-MIMO scenarios
    register their near-far ``user_power_db`` profiles strongest-first,
    so index order is received-power order and each cancellation stage
    removes the dominant remaining interferer.  Stage ``k`` therefore
    sees only streams ``k..n_tx-1``: its MMSE solve is smaller *and*
    cleaner than the joint LMMSE's, which is where the SIC sum-goodput
    gain comes from.

    Returns (x_hat (B, n_sc, n_tx), nv_eff (B, n_sc, n_tx)) — per
    *original* stream, same contract as :func:`mimo_mmse_detect_ext`.
    """
    n_tx = h.shape[-1]
    y_res = y
    xs, nvs = [], []
    for k in range(n_tx):
        x_all, nv_all = mimo_mmse_detect_ext(y_res, h[..., k:], noise_var)
        x_k, nv_k = x_all[..., 0], nv_all[..., 0]
        xs.append(x_k)
        nvs.append(nv_k)
        if k < n_tx - 1:
            # hard re-modulation: per-axis max-log hard bits back through
            # the modem = the nearest constellation point (gray square QAM)
            hard = (modem.demod_llr(x_k, nv_k) > 0).astype(jnp.int32)
            y_res = y_res - h[..., k] * modem.mod(hard)[..., None]
    return jnp.stack(xs, axis=-1), jnp.stack(nvs, axis=-1)


def ls_channel_estimate_link(
    y: jax.Array,  # (B, n_sym, n_sc, n_rx) received grid
    pilot_seq: jax.Array,  # (n_sc,) known pilot symbols
    pilot_masks: jax.Array,  # (n_tx, n_sym, n_sc) staggered per-tx combs
    pilot_stride: int,
) -> jax.Array:
    """Per-(rx, tx) LS estimate from staggered DMRS combs + interpolation.

    Each tx is sounded on its own comb (others silent there), so the LS
    estimate at tx t's pilot REs is interference-free.  Returns
    H_hat (B, n_sc, n_rx, n_tx), flat in time within the slot.
    """
    n_tx = pilot_masks.shape[0]
    b, n_sym, n_sc, n_rx = y.shape
    spacing = pilot_stride * n_tx
    est = y / pilot_seq[None, None, :, None]  # (B, n_sym, n_sc, n_rx)
    pos = jnp.arange(n_sc, dtype=jnp.float32)

    def interp_batch(xp, fp):  # fp (B*n_rx, n_p) complex
        re = jax.vmap(lambda f: jnp.interp(pos, xp, f))(jnp.real(fp))
        im = jax.vmap(lambda f: jnp.interp(pos, xp, f))(jnp.imag(fp))
        return re + 1j * im

    outs = []
    for t in range(n_tx):
        w = pilot_masks[t].astype(jnp.float32)[None, :, :, None]
        h_p = jnp.sum(est * w, axis=1) / jnp.maximum(
            jnp.sum(w, axis=1), 1e-9
        )  # (B, n_sc, n_rx), nonzero only on tx t's comb
        p_idx = jnp.arange(t * pilot_stride, n_sc, spacing)
        fp = jnp.moveaxis(h_p[:, p_idx, :], 1, -1)  # (B, n_rx, n_p)
        full = interp_batch(
            pos[p_idx], fp.reshape(b * n_rx, -1)
        ).reshape(b, n_rx, n_sc)
        outs.append(jnp.moveaxis(full, 1, -1))  # (B, n_sc, n_rx)
    return jnp.stack(outs, axis=-1)  # (B, n_sc, n_rx, n_tx)


def mmse_smooth_link(
    h_ls: jax.Array,  # (B, n_sc, n_rx, n_tx)
    w: jax.Array,  # (n_sc, n_sc) from wiener_filter
) -> jax.Array:
    """Wiener smoothing of a per-(rx, tx) LS estimate (folds antenna pairs
    into the batch of :func:`wiener_apply`)."""
    b, n_sc, n_rx, n_tx = h_ls.shape
    flat = jnp.moveaxis(h_ls, 1, -1).reshape(b * n_rx * n_tx, n_sc)
    sm = wiener_apply(w, flat)
    return jnp.moveaxis(sm.reshape(b, n_rx, n_tx, n_sc), -1, 1)
