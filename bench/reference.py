"""Plain reference receiver: the configuration's receive chain, written out.

DFT -> LS channel estimate on the DMRS combs with clamped linear
interpolation -> Wiener smoothing -> unbiased MMSE (or SIC) detection
with max-log LLRs -> de-rate-matching and HARQ combining -> layered
normalized min-sum LDPC decode -> CRC-16 check.

Split-complex arithmetic in one dtype throughout, so the same code runs
as the reference (float32, matmuls at ``highest`` precision) and as the
precision control (bfloat16: the step below the float32 the
configuration states).  Only the Wiener filter's linear solve, which has
no bfloat16 kernel, is solved in float64 on the host and rounded to the
dtype.  It imports nothing of the program under test and takes nothing
that the program made: every table comes from :mod:`phy`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from phy import Rung, crc_matrix

CHUNK = 16  # slots per reference call (the tail is padded)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _csolve(ar, ai, br, bi):
    """Gauss-Jordan solve of A Z = B, split complex, no pivoting (A is
    Hermitian positive definite).  A (..., T, T), B (..., T, M)."""
    t = ar.shape[-1]
    for k in range(t):
        dr, di = ar[..., k, k], ai[..., k, k]
        den = dr * dr + di * di
        ivr, ivi = (dr / den)[..., None], (-di / den)[..., None]
        rar, rai = _cmul(ar[..., k, :], ai[..., k, :], ivr, ivi)
        rbr, rbi = _cmul(br[..., k, :], bi[..., k, :], ivr, ivi)
        for i in range(t):
            if i == k:
                continue
            fr, fi = ar[..., i, k][..., None], ai[..., i, k][..., None]
            pr, pi = _cmul(fr, fi, rar, rai)
            ar = ar.at[..., i, :].add(-pr)
            ai = ai.at[..., i, :].add(-pi)
            pr, pi = _cmul(fr, fi, rbr, rbi)
            br = br.at[..., i, :].add(-pr)
            bi = bi.at[..., i, :].add(-pi)
        ar, ai = ar.at[..., k, :].set(rar), ai.at[..., k, :].set(rai)
        br, bi = br.at[..., k, :].set(rbr), bi.at[..., k, :].set(rbi)
    return br, bi


def _interp_matrix(r: Rung, t: int) -> np.ndarray:
    """(n_p, n_sc) clamped linear interpolation from tx t's comb."""
    p_idx = np.arange(t * r.pilot_stride, r.n_sc, r.pilot_stride * r.n_tx)
    w = np.zeros((len(p_idx), r.n_sc))
    for s in range(r.n_sc):
        if s <= p_idx[0]:
            w[0, s] = 1.0
        elif s >= p_idx[-1]:
            w[-1, s] = 1.0
        else:
            i = int(np.searchsorted(p_idx, s, side="right") - 1)
            f = (s - p_idx[i]) / (p_idx[i + 1] - p_idx[i])
            w[i, s], w[i + 1, s] = 1.0 - f, f
    return w


@functools.lru_cache(maxsize=None)
def wiener(n_sc: int, corr_len: float, noise_var: float) -> np.ndarray:
    """S = (R + nv I)^-1 R for the exponential frequency correlation R;
    the smoothed estimate is S @ h_ls."""
    d = np.abs(np.arange(n_sc)[:, None] - np.arange(n_sc)[None, :])
    rr = np.exp(-d / corr_len)
    return np.linalg.solve(rr + noise_var * np.eye(n_sc), rr)


def _axis_llrs(comp, r: Rung, nvs):
    """Max-log LLRs (log P(1)/P(0)) of one axis' bits, MSB first."""
    nb = r.bits_per_symbol // 2
    scale = float(np.sqrt(r.norm))
    d = [(comp * scale - lv) ** 2 for lv in r.levels]
    out = []
    for p in range(nb):
        ones = [j for j in range(len(r.levels)) if (j >> (nb - 1 - p)) & 1]
        zeros = [j for j in range(len(r.levels)) if j not in ones]
        d0 = functools.reduce(jnp.minimum, [d[j] for j in zeros])
        d1 = functools.reduce(jnp.minimum, [d[j] for j in ones])
        out.append((d0 - d1) / nvs)
    return out


def _hard(comp, r: Rung):
    """Nearest axis level of an unbiased estimate, back in unit power."""
    scale = float(np.sqrt(r.norm))
    v = comp * scale
    best, best_d = jnp.full_like(v, r.levels[0]), (v - r.levels[0]) ** 2
    for lv in r.levels[1:]:
        d = (v - lv) ** 2
        best = jnp.where(d < best_d, lv, best)
        best_d = jnp.minimum(d, best_d)
    return best / scale


def _mmse(yr, yi, hr, hi, nv, r: Rung):
    """Unbiased MMSE of every stream in h; LLRs (..., T, bits)."""
    t = hr.shape[-1]
    # y (N, S, F, R); h (N, S, F, R, T)
    gr = (jnp.einsum("nsfrt,nsfru->nsftu", hr, hr)
          + jnp.einsum("nsfrt,nsfru->nsftu", hi, hi))
    gi = (jnp.einsum("nsfrt,nsfru->nsftu", hr, hi)
          - jnp.einsum("nsfrt,nsfru->nsftu", hi, hr))
    br = (jnp.einsum("nsfrt,nsfr->nsft", hr, yr)
          + jnp.einsum("nsfrt,nsfr->nsft", hi, yi))
    bi = (jnp.einsum("nsfrt,nsfr->nsft", hr, yi)
          - jnp.einsum("nsfrt,nsfr->nsft", hi, yr))
    shape = br.shape + (t,)
    eye = jnp.eye(t, dtype=gr.dtype)
    ar = jnp.broadcast_to(gr + nv * eye, shape)
    ai = jnp.broadcast_to(gi, shape)
    rhs_r = jnp.concatenate([br[..., None], jnp.broadcast_to(gr, shape)], -1)
    rhs_i = jnp.concatenate([bi[..., None], jnp.broadcast_to(gi, shape)], -1)
    zr, zi = _csolve(ar, ai, rhs_r, rhs_i)
    mu = jnp.clip(jnp.diagonal(zr[..., 1:], axis1=-2, axis2=-1),
                  1e-6, 1.0 - 1e-6)
    ux, uy = zr[..., 0] / mu, zi[..., 0] / mu
    nvs = jnp.maximum((1.0 - mu) / mu * r.norm, 1e-6)
    llr = jnp.stack(_axis_llrs(ux, r, nvs) + _axis_llrs(uy, r, nvs), -1)
    return ux, uy, llr


def _detect(yr, yi, hr, hi, nv, r: Rung):
    """(N, S, F, T, bits) LLRs: joint MMSE, or SIC in stream order."""
    if not r.sic:
        return _mmse(yr, yi, hr, hi, nv, r)[2]
    out = []
    for k in range(r.n_tx):
        ux, uy, llr = _mmse(yr, yi, hr[..., k:], hi[..., k:], nv, r)
        out.append(llr[..., 0, :])
        if k < r.n_tx - 1:
            xr, xi = _hard(ux[..., 0], r), _hard(uy[..., 0], r)
            cr, ci = _cmul(hr[..., k], hi[..., k], xr[..., None],
                           xi[..., None])
            yr, yi = yr - cr, yi - ci
    return jnp.stack(out, -2)


def _decode(llr, code, dt):
    """Layered normalized min-sum.  llr (M, n) in log P(1)/P(0); returns
    (v (M, n_b, z) in log P(0)/P(1), iterations (M,))."""
    m = llr.shape[0]
    layers = code.layers()
    alpha = jnp.asarray(code.alpha, dt)
    roll = lambda x, s: jnp.roll(x, s, axis=-1) if s % code.z else x

    def syndrome_ok(v):
        hard = (v < 0).astype(jnp.int32)
        bad = jnp.zeros((m, code.z), jnp.int32)
        for edges in layers:
            p = functools.reduce(
                jnp.bitwise_xor, [roll(hard[:, c], -s) for c, s in edges])
            bad = bad | p
        return jnp.all(bad == 0, axis=-1)

    def sweep(v, c2v):
        new = []
        for li, edges in enumerate(layers):
            t = jnp.stack([roll(v[:, c], -s) for c, s in edges]) - c2v[li]
            at = jnp.abs(t)
            m1 = jnp.min(at, axis=0)
            first = jnp.argmax(at == m1[None], axis=0)
            is_min = jnp.arange(len(edges))[:, None, None] == first[None]
            m2 = jnp.min(jnp.where(is_min, jnp.inf, at), axis=0)
            mag = jnp.where(is_min, m2[None], m1[None])
            neg = t < 0
            odd = jnp.sum(neg, axis=0) % 2 == 1
            upd = jnp.where(neg != odd[None], -alpha, alpha) * mag
            vn = t + upd
            for e, (c, s) in enumerate(edges):
                v = v.at[:, c].set(roll(vn[e], s))
            new.append(upd)
        return v, tuple(new)

    v0 = -llr.reshape(m, code.n_b, code.z).astype(dt)
    c2v0 = tuple(jnp.zeros((len(e), m, code.z), dt) for e in layers)
    done0 = syndrome_ok(v0)

    def cond(carry):
        it, _, _, done, _ = carry
        return (it < code.max_iters) & ~jnp.all(done)

    def body(carry):
        it, v, c2v, done, iters = carry
        vn, c2vn = sweep(v, c2v)
        keep = done[:, None, None]
        v = jnp.where(keep, v, vn)
        c2v = tuple(jnp.where(done[None, :, None], a, b)
                    for a, b in zip(c2v, c2vn))
        iters = iters + (~done).astype(jnp.int32)
        return it + 1, v, c2v, done | syndrome_ok(v), iters

    _, v, _, _, iters = jax.lax.while_loop(
        cond, body, (0, v0, c2v0, done0, jnp.zeros(m, jnp.int32)))
    return v, iters


@functools.partial(jax.jit, static_argnums=(0, 1))
def _receive(r: Rung, dtype: str, ytr, yti, smooth, nv, rv, prior):
    dt = jnp.dtype(dtype)
    code = r.code
    n = ytr.shape[0]
    yr, yi = ytr.astype(dt), yti.astype(dt)
    # DFT along the subcarrier axis
    L = r.n_sc
    ang = -2.0 * np.pi * np.outer(np.arange(L), np.arange(L)) / L
    fr = jnp.asarray(np.cos(ang), dt)
    fi = jnp.asarray(np.sin(ang), dt)
    yr, yi = (jnp.einsum("nslr,kl->nskr", yr, fr)
              - jnp.einsum("nslr,kl->nskr", yi, fi),
              jnp.einsum("nslr,kl->nskr", yr, fi)
              + jnp.einsum("nslr,kl->nskr", yi, fr))
    # LS on each tx's comb, averaged over the DMRS symbols, interpolated
    seq = r.pilot_seq()
    psym = np.asarray(r.pilot_symbols)
    hr, hi = [], []
    for t in range(r.n_tx):
        p_idx = np.arange(t * r.pilot_stride, r.n_sc,
                          r.pilot_stride * r.n_tx)
        er = jnp.mean(yr[:, psym][:, :, p_idx], axis=1)  # (N, P, R)
        ei = jnp.mean(yi[:, psym][:, :, p_idx], axis=1)
        sr = jnp.asarray(np.real(np.conj(seq[p_idx])), dt)[None, :, None]
        si = jnp.asarray(np.imag(np.conj(seq[p_idx])), dt)[None, :, None]
        lr, li = _cmul(er, ei, sr, si)
        w = jnp.asarray(_interp_matrix(r, t), dt)
        hr.append(jnp.einsum("npr,ps->nsr", lr, w))
        hi.append(jnp.einsum("npr,ps->nsr", li, w))
    hr, hi = jnp.stack(hr, -1), jnp.stack(hi, -1)  # (N, F, R, T)
    if r.mmse_smooth:
        s = smooth.astype(dt)
        hr = jnp.einsum("fk,nkrt->nfrt", s, hr)
        hi = jnp.einsum("fk,nkrt->nfrt", s, hi)
    shape = yr.shape + (r.n_tx,)
    llr = _detect(yr, yi, jnp.broadcast_to(hr[:, None], shape),
                  jnp.broadcast_to(hi[:, None], shape), nv.astype(dt), r)
    # canonical data-RE order -> per-codeword windows
    sym, sc = r.data_re()
    c, e = r.codewords, code.e_bits
    llr_e = llr[:, sym, sc].reshape(n, -1)[:, : c * e].reshape(n, c, e)
    buf = jnp.concatenate(
        [llr_e, jnp.zeros((n, c, code.n_mother - e), dt)], -1)
    off = ((rv % 4) * code.n_b) // 4 * code.z
    idx = jnp.mod(jnp.arange(code.n_mother)[None, :] - off[:, None],
                  code.n_mother)
    buf = jnp.take_along_axis(buf, idx[:, None, :], axis=-1)
    cw_llr = buf + prior.astype(dt)
    v, iters = _decode(cw_llr.reshape(n * c, -1), code, dt)
    hard = (v.reshape(n, c, -1)[..., : code.k] < 0).astype(jnp.int8)
    return cw_llr.astype(jnp.float32), hard, iters.reshape(n, c)


def receive(r: Rung, y_time: np.ndarray, noise_var: np.ndarray,
            rv: np.ndarray, prior: np.ndarray, dtype: str = "float32"):
    """Reference receive of N slots of rung ``r``.

    y_time (N, n_sym, n_sc, n_rx) complex, noise_var (N,), rv (N,),
    prior (N, C, n_mother).  Returns numpy ``cw_llr`` (N, C, n_mother),
    ``crc_ok`` (N, C) and ``iters`` (N, C).  Slots go through in chunks
    of :data:`CHUNK`, grouped by noise variance (one Wiener filter each).
    """
    n = len(y_time)
    out = {"cw_llr": np.zeros((n, r.codewords, r.code.n_mother), np.float32),
           "crc_ok": np.zeros((n, r.codewords), bool),
           "iters": np.zeros((n, r.codewords), np.int32)}
    m = crc_matrix(r.code.k_info, r.code.crc_poly, r.code.crc_bits)
    prec = "highest" if dtype == "float32" else "default"
    for nv in np.unique(noise_var):
        sel = np.flatnonzero(noise_var == nv)
        smooth = wiener(r.n_sc, r.corr_len, float(nv)).astype(np.float32)
        for i in range(0, len(sel), CHUNK):
            part = sel[i : i + CHUNK]
            take = np.concatenate(
                [part, np.repeat(part[:1], CHUNK - len(part))])
            with jax.default_matmul_precision(prec):
                cw, hard, it = jax.device_get(_receive(
                    r, dtype,
                    np.real(y_time[take]).astype(np.float32),
                    np.imag(y_time[take]).astype(np.float32),
                    smooth, np.float32(nv), rv[take].astype(np.int32),
                    prior[take].astype(np.float32)))
            k = len(part)
            hard = hard[:k].astype(np.int32)
            info, crc = hard[..., : r.code.k_info], hard[..., r.code.k_info:]
            out["cw_llr"][part] = cw[:k]
            out["crc_ok"][part] = np.all(info @ m % 2 == crc, axis=-1)
            out["iters"][part] = it[:k]
    return out
