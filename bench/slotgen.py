"""Coded uplink slot generator for the open-loop traffic mixes.

Draws transport blocks, CRC-attaches, LDPC-encodes and rate-matches them
at RV 0, lays the coded bits onto the data REs in canonical order, maps
them to gray QAM with the DMRS combs embedded, and sends the grid through
a Rayleigh TDL channel (exponential power-delay profile, optional
per-stream near-far gains) with AWGN.  One jitted call makes each chunk
of the pool on the device; the slots come back to the host in the program's
slot schema, one dict per slot with a leading batch axis of 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from phy import Rung, crc_matrix


def _encode(code, bits):
    """Systematic dual-diagonal QC-LDPC encode: (N, C, k) -> (N, C, n)."""
    u = bits.reshape(bits.shape[:-1] + (code.k_b, code.z))
    synd = []
    for edges in code.info_edges:
        s = jnp.zeros(u.shape[:-2] + (code.z,), jnp.int32)
        for c, sh in edges:
            s = s + jnp.roll(u[..., c, :], -sh, axis=-1)
        synd.append(s)
    p = jnp.mod(jnp.cumsum(jnp.stack(synd, axis=-2), axis=-2), 2)
    return jnp.concatenate([u, p], axis=-2).reshape(
        bits.shape[:-1] + (code.n_mother,))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _pool(r: Rung, key, n: int) -> dict:
    code, c = r.code, r.codewords
    nb = r.bits_per_symbol
    k_info, k_fill, k_ch, k_n = jax.random.split(key, 4)
    info = jax.random.bernoulli(k_info, 0.5, (n, c, code.k_info)).astype(
        jnp.int32)
    m = jnp.asarray(crc_matrix(code.k_info, code.crc_poly, code.crc_bits),
                    jnp.float32)
    crc = jnp.mod(jnp.einsum("nck,kr->ncr", info.astype(jnp.float32), m),
                  2.0).astype(jnp.int32)
    cw = _encode(code, jnp.concatenate([info, crc], axis=-1))
    flat = cw[..., : code.e_bits].reshape(n, c * code.e_bits)
    n_fill = r.data_bits - c * code.e_bits
    if n_fill:
        fill = jax.random.bernoulli(k_fill, 0.5, (n, n_fill)).astype(
            jnp.int32)
        flat = jnp.concatenate([flat, fill], axis=-1)
    sym, sc = r.data_re()
    bits = jnp.zeros((n, r.n_sym, r.n_sc, r.n_tx, nb), jnp.int32)
    bits = bits.at[:, sym, sc].set(flat.reshape(n, len(sym), r.n_tx, nb))

    half = nb // 2
    lv = jnp.asarray(r.levels, jnp.float32)
    w = 2 ** jnp.arange(half - 1, -1, -1)
    ire = jnp.sum(bits[..., :half] * w, axis=-1)
    iim = jnp.sum(bits[..., half:] * w, axis=-1)
    x = (lv[ire] + 1j * lv[iim]) / np.sqrt(r.norm)
    pm = r.pilot_masks()
    union = pm.any(axis=0)
    seq = jnp.asarray(r.pilot_seq(), jnp.complex64)
    x = jnp.where(np.moveaxis(pm, 0, -1)[None], seq[None, None, :, None],
                  jnp.where(union[None, ..., None], 0.0, x))

    pdp = np.exp(-np.arange(r.n_taps) / r.delay_spread)
    pdp = pdp / pdp.sum()
    kr, ki = jax.random.split(k_ch)
    shape = (n, r.n_rx, r.n_tx, r.n_taps)
    taps = (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape))
    taps = taps * np.sqrt(pdp / 2.0)
    h = jnp.fft.fft(taps, n=r.fft_size, axis=-1)[..., : r.n_sc]
    h = jnp.moveaxis(h, -1, 1)[:, None]  # (n, 1, n_sc, n_rx, n_tx)
    if r.user_power_db:
        h = h * jnp.asarray([10.0 ** (p / 20.0) for p in r.user_power_db],
                            jnp.float32)
    y = jnp.einsum("nsrt,nmst->nmsr", h[:, 0], x)
    k1, k2 = jax.random.split(k_n)
    noise = jax.random.normal(k1, y.shape) + 1j * jax.random.normal(
        k2, y.shape)
    y = y + noise * np.sqrt(r.noise_var / 2.0)
    return {
        "y_time": jnp.fft.ifft(y, axis=2).astype(jnp.complex64),
        "y": y.astype(jnp.complex64), "x": x.astype(jnp.complex64),
        "h": h.astype(jnp.complex64), "bits": bits, "info_bits": info,
    }


def make_pool(r: Rung, key, n: int, chunk: int) -> list:
    """``n`` independent slots of rung ``r`` as host-side slot dicts, made
    ``chunk`` at a time by one executable (``n`` a multiple of ``chunk``)."""
    side = {
        "noise_var": np.float32(r.noise_var),
        "pilot_seq": r.pilot_seq().astype(np.complex64),
        "pilot_masks": r.pilot_masks(),
        "data_mask": ~r.pilot_masks().any(axis=0),
    }
    out = []
    for c in range(n // chunk):
        batched = jax.device_get(_pool(r, jax.random.fold_in(key, c), chunk))
        out += [{**{k: v[i : i + 1] for k, v in batched.items()}, **side}
                for i in range(chunk)]
    return out
