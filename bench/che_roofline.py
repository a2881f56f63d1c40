"""Operations and bytes the LS channel estimate needs per slot, from
shapes alone, whatever implements it (``rx_ls_che_roofline``).

Operations, in real arithmetic (a complex multiply-add counts 8): the
pilot-symbol average of every DMRS RE of every receive antenna (2 per
pilot RE and pilot symbol), then for each subcarrier, receive antenna
and transmit stream the clamped linear interpolation between two pilots
with the pilot divide folded into its weights (2 complex multiply-adds).
Bytes: the pilot REs read and the LS estimate written, complex64.  The
interpolation weights are per scenario and not counted.
"""
from __future__ import annotations

from phy import Rung

C64 = 8  # bytes of one complex64


def pilot_res(r: Rung) -> int:
    """DMRS REs of one slot, over the pilot symbols and every comb."""
    return len(r.pilot_symbols) * r.n_sc // r.pilot_stride


def ops_per_slot(r: Rung) -> float:
    average = 2.0 * pilot_res(r) * r.n_rx
    interp = 2 * 8.0 * r.n_sc * r.n_rx * r.n_tx
    return average + interp


def bytes_per_slot(r: Rung) -> float:
    return float(C64 * (pilot_res(r) * r.n_rx + r.n_sc * r.n_rx * r.n_tx))


def roofline_s_per_slot(r: Rung, peaks: dict) -> float:
    """Least device time of one slot's LS estimate on a chip with
    ``peaks``: the larger of its compute and its memory time."""
    return max(ops_per_slot(r) / peaks["bf16_flops"],
               bytes_per_slot(r) / peaks["hbm_bytes_per_s"])
