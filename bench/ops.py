"""Operations of the receiver's work per slot, from shapes alone.

Per-slot operation counts follow the shape arithmetic of the receive
chain (FFT, LS-CHE interpolation GEMM or comb interpolation, Wiener
smoothing, detection and demapping, one LDPC iteration per codeword), in
real arithmetic: a complex multiply-add counts 8 operations.
"""
from __future__ import annotations

import math

from phy import Rung


def _levels(r: Rung) -> int:
    return 2 ** (r.bits_per_symbol // 2)


def _res(r: Rung) -> int:
    return r.n_sym * r.n_sc


def fft(r: Rung) -> float:
    return r.n_sym * r.n_rx * 5.0 * r.n_sc * math.log2(r.n_sc)


def ls_che(r: Rung, fused: bool) -> float:
    n_p = r.n_sc // (r.pilot_stride * r.n_tx)
    n_psym = len(r.pilot_symbols)
    if fused:  # pilot average + split-complex interpolation GEMM
        return (2.0 * n_psym * r.n_tx * n_p * r.n_rx
                + 8.0 * r.n_rx * r.n_tx * n_p * r.n_sc)
    return n_psym * r.n_sc * r.n_rx * 10.0 + r.n_sc * r.n_rx * r.n_tx * 8.0


def smooth(r: Rung) -> float:
    return 8.0 * r.n_sc * r.n_sc * r.n_rx * r.n_tx if r.mmse_smooth else 0.0


def detect_demap(r: Rung) -> float:
    """Gram, Gauss solve, right-hand side and max-log demap per RE; SIC
    solves a shrinking system per stage and cancels each stream."""
    t, rx, lv = r.n_tx, r.n_rx, _levels(r)
    if r.sic:
        per_re = (sum(8.0 * (m * m * rx + m ** 3 + m * rx)
                      for m in range(1, t + 1))
                  + t * lv * 8.0 + (t - 1) * 8.0 * rx)
    else:
        per_re = 8.0 * (t * t * rx + t ** 3 + t * rx) + t * lv * 8.0
    return _res(r) * per_re


def ldpc_iteration(r: Rung) -> float:
    """One layered min-sum sweep plus the syndrome check, per slot."""
    edges = sum(len(e) for e in r.code.layers())
    return r.codewords * edges * r.code.z * 10.0


def per_slot(r: Rung, fused: bool) -> float:
    """Receiver operations per slot, LDPC at one iteration."""
    return (fft(r) + ls_che(r, fused) + smooth(r) + detect_demap(r)
            + ldpc_iteration(r))
