"""Unified receiver-pipeline subsystem (paper §II/§V: AI-native PHY).

A :class:`ReceiverPipeline` is a sequence of :class:`RxStage`\\ s.  Each
stage declares

  * which TensorPool engine does the work (``compute``: "TE" tensor
    engines, "PE" the RV32 cores, "DMA" the L2<->L1 movers),
  * a pure ``apply`` function threading a state dict (the slot) through
    the stage, and
  * a ``cycles`` estimator returning a :class:`repro.core.pool.BlockCycles`
    for one slot, so the pipeline can report its TTI budget per stage.

The classical chain (CFFT -> LS/MMSE CHE -> MIMO-MMSE detect -> max-log
LLR demod) and both neural receivers (DeepRx, CE-ViT + detect) are
registered behind this one interface; the neural hot paths run through the
fused Pallas kernels in :mod:`repro.kernels.ops`.  Coded scenarios append
a CRC + LDPC decode stage (:mod:`repro.phy.coding`,
:mod:`repro.kernels.ldpc`), so those chains run bits-in -> bits-out and
are BLER-scored.

Pipelines operate on the unified link-slot schema of
:func:`repro.phy.ofdm.make_link_slot` (SISO through MIMO, static or
Doppler), and the whole chain is one jitted end-to-end function over a
batch of slots.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pool
from repro.kernels import quant, rx_fused
from repro.phy import classical, coding, models, ofdm
from repro.phy.scenarios import LinkScenario

_C16 = 4  # bytes per complex64 element when streamed as 2 x fp16


@dataclasses.dataclass(frozen=True)
class RxStage:
    """One receiver stage: compute-class + apply + cycle estimator.

    ``cycles`` may be None for stages without a TensorPool cost model
    (e.g. experimental receivers); the pipeline's budget methods then
    skip the stage and reports degrade gracefully.
    """
    name: str
    compute: str  # dominant engine: "TE" | "PE" | "DMA"
    apply: Callable[[dict], dict]
    cycles: Optional[Callable[[], pool.BlockCycles]] = None


def _sum_cycles(cs) -> pool.BlockCycles:
    cs = list(cs)
    return pool.BlockCycles(
        te_cycles=sum(c.te_cycles for c in cs),
        pe_cycles=sum(c.pe_cycles for c in cs),
        dma_cycles=sum(c.dma_cycles for c in cs),
    )


class ReceiverPipeline:
    """A named chain of RxStages over the unified link-slot schema.

    ``run`` executes the whole chain as one jitted function; the cycle
    methods report the TensorPool budget without running anything.
    """

    def __init__(self, name: str, stages: list[RxStage],
                 scenario: LinkScenario, params=None,
                 precision: str = "fp32"):
        self.name = name
        self.stages = tuple(stages)
        self.scenario = scenario
        self.params = params  # neural weights, None for classical chains
        # numeric policy of the served datapath (see repro.kernels.quant);
        # the energy model prices TE MACs and operand traffic at this
        self.precision = quant.resolve_precision(precision)
        self._jitted = jax.jit(self._apply)

    def _apply(self, slot: dict) -> dict:
        state = dict(slot)
        for st in self.stages:
            # names the stage's ops (``op_name``) in the compiled program
            with jax.named_scope(st.name):
                state = st.apply(state)
        return state

    def run(self, slot: dict) -> dict:
        """Jitted end-to-end receive over a batch of slots."""
        return self._jitted(slot)

    # -- TensorPool budget ------------------------------------------------
    def stage_cycles(self) -> dict[str, pool.BlockCycles]:
        """Per-stage BlockCycles; stages without an estimator are skipped."""
        return {
            st.name: st.cycles() for st in self.stages
            if st.cycles is not None
        }

    def total_cycles(self) -> pool.BlockCycles:
        return _sum_cycles(
            st.cycles() for st in self.stages if st.cycles is not None
        )

    def tti_report(self, batch: int = 1, clock_hz: float = 1e9,
                   tti_s: float = 1e-3) -> dict:
        """Per-engine ms and the 1 ms TTI utilization for ``batch`` slots."""
        tot = self.total_cycles()
        to_ms = lambda cyc: batch * cyc / clock_hz * 1e3
        conc_ms = to_ms(tot.concurrent())
        return {
            "te_ms": to_ms(tot.te_cycles),
            "pe_ms": to_ms(tot.pe_cycles),
            "dma_ms": to_ms(tot.dma_cycles),
            "sequential_ms": to_ms(tot.sequential),
            "concurrent_ms": conc_ms,
            "tti_utilization": conc_ms / (tti_s * 1e3),
            "fits_tti": bool(conc_ms <= tti_s * 1e3),
        }

    def energy_report(self, clock_hz: float = 1e9):
        """Per-slot modeled :class:`repro.analysis.costmodel.EnergyReport`
        at this pipeline's precision policy."""
        from repro.analysis import costmodel

        return costmodel.pipeline_energy(self, clock_hz=clock_hz)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def slot_metrics(state: dict, scenario: LinkScenario,
                 per_slot: bool = False) -> dict:
    """BER / channel-MSE / EVM from a finished pipeline state.

    ``per_slot=True`` returns (B,) arrays instead of batch means.
    """
    red_axes = lambda x: tuple(range(1, x.ndim)) if per_slot else None
    data_mask = state.get("data_mask")  # (n_sym, n_sc)
    if data_mask is None:
        data_mask = ~jnp.any(ofdm.link_pilot_masks(scenario.grid), axis=0)
    out = {}
    if "llr" in state and "bits" in state:
        hard = (state["llr"] > 0).astype(jnp.int32)
        err = (hard != state["bits"]).astype(jnp.float32)
        m = data_mask[None, :, :, None, None].astype(jnp.float32)
        w = err * m
        denom = jnp.sum(
            jnp.broadcast_to(m, err.shape), axis=red_axes(err)
        )
        out["ber"] = jnp.sum(w, axis=red_axes(err)) / denom
    h_est = state.get("h_hat", state.get("h_ls"))
    if h_est is not None and "h" in state:
        h_bar = jnp.mean(state["h"], axis=1)  # (B, n_sc, n_rx, n_tx)
        e = jnp.abs(h_est - h_bar) ** 2
        out["che_mse"] = jnp.mean(e, axis=red_axes(e))
    if "x_hat" in state and "x" in state:
        e = jnp.abs(state["x_hat"] - state["x"]) ** 2
        m = data_mask[None, :, :, None].astype(jnp.float32)
        denom = jnp.sum(jnp.broadcast_to(m, e.shape), axis=red_axes(e))
        out["evm"] = jnp.sum(e * m, axis=red_axes(e)) / denom
    if "info_bits_hat" in state and "info_bits" in state:
        # coded link: block error rate over the slot's transport blocks
        # (a block fails when any payload bit decodes wrong) + decode
        # effort (layered min-sum iterations until the syndrome cleared)
        blk = jnp.any(
            state["info_bits_hat"] != state["info_bits"], axis=-1
        ).astype(jnp.float32)  # (B, C)
        out["bler"] = jnp.mean(blk, axis=red_axes(blk))
        it = state["decode_iters"].astype(jnp.float32)
        out["decode_iters"] = jnp.mean(it, axis=red_axes(it))
    return out


# ---------------------------------------------------------------------------
# Stage factories (cycle models use the paper's pool constants; all
# estimates are per slot, batch scaling happens in tti_report)
# ---------------------------------------------------------------------------

def _grid_bytes(cfg: ofdm.GridConfig, per_re: int = 1) -> float:
    return cfg.n_symbols * cfg.n_subcarriers * per_re * _C16


def cfft_stage(cfg: ofdm.GridConfig) -> RxStage:
    def apply(state):
        # length-agnostic dispatch: native FFT on any symbol length (the
        # radix-2 PE butterfly stays opt-in via prefer_butterfly)
        state["y"] = classical.cfft_auto(state["y_time"], axis=2)
        return state

    def cycles():
        flops = (cfg.n_symbols * cfg.n_rx
                 * 5.0 * cfg.fft_size * math.log2(cfg.fft_size))
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.7),
            dma_cycles=pool.dma_cycles(2 * _grid_bytes(cfg, cfg.n_rx)),
        )

    return RxStage("cfft", "PE", apply, cycles)


def ls_che_stage(cfg: ofdm.GridConfig, fused: bool = False) -> RxStage:
    """LS CHE on the staggered DMRS combs.

    ``fused=True`` routes through :mod:`repro.kernels.rx_fused`: comb
    extract → per-pilot divide → frequency interpolation folded into one
    banded complex GEMM per subcarrier tile against a precomputed operator
    — TE work with the per-pilot estimates resident in L1, instead of the
    PE gather/lerp.
    """
    seq = ofdm.pilot_sequence(cfg)
    n_sc, n_psym = cfg.n_subcarriers, len(cfg.pilot_symbols)
    if fused:
        op = rx_fused.make_ls_interp_operator(
            n_sc, cfg.n_tx, cfg.pilot_stride, np.asarray(seq)
        )
        n_p, width, n_pad = op.n_p, op.width, op.band.shape[-1]

        def apply(state):
            state["h_ls"] = rx_fused.ls_che(
                state["y"], cfg.pilot_symbols, cfg.pilot_stride, op
            )
            return state

        def cycles():
            # split-complex banded interp GEMM on the TEs (each tile
            # reads its pilot window); pilot averaging on PEs
            macs = 4.0 * cfg.n_rx * cfg.n_tx * width * n_pad
            flops = 2.0 * n_psym * cfg.n_tx * n_p * cfg.n_rx
            return pool.BlockCycles(
                te_cycles=pool.te_cycles(macs, utilization=0.67),
                pe_cycles=pool.pe_cycles(flops, ipc=0.7),
                dma_cycles=pool.dma_cycles(
                    # pilot symbols in + H out; the static operator is
                    # per-scenario resident, the per-pilot LS grid never
                    # round-trips
                    n_psym * n_sc * cfg.n_rx * _C16
                    + n_sc * cfg.n_rx * cfg.n_tx * _C16
                ),
            )

        return RxStage("ls_che_fused", "TE", apply, cycles)

    masks = ofdm.link_pilot_masks(cfg)

    def apply(state):
        state["h_ls"] = classical.ls_channel_estimate_link(
            state["y"], seq, masks, cfg.pilot_stride
        )
        return state

    def cycles():
        flops = (n_psym * cfg.n_subcarriers * cfg.n_rx * 10.0  # LS + avg
                 + cfg.n_subcarriers * cfg.n_rx * cfg.n_tx * 8.0)  # interp
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.6),
            dma_cycles=pool.dma_cycles(
                _grid_bytes(cfg, cfg.n_rx)
                + cfg.n_subcarriers * cfg.n_rx * cfg.n_tx * _C16
            ),
        )

    return RxStage("ls_che", "PE", apply, cycles)


def mmse_che_stage(cfg: ofdm.GridConfig, corr_len: float = 16.0) -> RxStage:
    """Wiener smoothing; the (n_sc x n_sc) filter is per-scenario and
    amortized, the per-slot work is the matrix-vector apply per antenna
    pair."""

    def apply(state):
        state["h_hat"] = classical.mmse_smooth_link(
            state["h_ls"], state["noise_var"], corr_len=corr_len
        )
        return state

    def cycles():
        n_sc = cfg.n_subcarriers
        flops = 8.0 * n_sc * n_sc * cfg.n_rx * cfg.n_tx
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.77),
            dma_cycles=pool.dma_cycles(
                2 * n_sc * cfg.n_rx * cfg.n_tx * _C16
            ),
        )

    return RxStage("mmse_che", "PE", apply, cycles)


def _broadcast_h(h_est, n_sym):
    b, n_sc, n_rx, n_tx = h_est.shape
    hb = jnp.broadcast_to(
        h_est[:, None], (b, n_sym, n_sc, n_rx, n_tx)
    )
    return hb.reshape(b * n_sym, n_sc, n_rx, n_tx)


def detect_demap_stage(cfg: ofdm.GridConfig, modem: ofdm.Modem,
                       precision: Optional[str] = None) -> RxStage:
    """Fused equalize→demap (replaces detect_stage + demod_stage).

    One :mod:`repro.kernels.rx_fused` pass per (batch, subcarrier) tile:
    Gram, in-register Gauss solve, unbiasing, and max-log LLRs — the
    ``h_eff`` / Gram / equalized-symbol grids stay in L1 instead of
    round-tripping between two stages.  ``precision="int8"|"fp8"`` emits
    LLRs on the quantized grid (see :func:`rx_fused.mmse_detect_demap`).
    """

    def apply(state):
        h_est = state.get("h_hat", state.get("h_ls"))
        x_hat, nv_eff, llr = rx_fused.mmse_detect_demap(
            state["y"], h_est, state["noise_var"], modem,
            precision=precision,
        )
        state["x_hat"], state["nv_eff"], state["llr"] = x_hat, nv_eff, llr
        return state

    def cycles():
        t, r = cfg.n_tx, cfg.n_rx
        lvl = 2 ** (modem.bits_per_symbol // 2)
        per_re = (8.0 * (t * t * r + t ** 3 + t * r)  # gram+solve+rhs
                  + t * lvl * 8.0)  # max-log demap
        flops = cfg.n_symbols * cfg.n_subcarriers * per_re
        return pool.BlockCycles(
            te_cycles=0.0,
            # fused straight-line inner loop: no intermediate loads/stores
            # between gram/solve/demap -> better issue rate than the two
            # separate stages (0.59 / 0.6)
            pe_cycles=pool.pe_cycles(flops, ipc=0.8),
            dma_cycles=pool.dma_cycles(
                _grid_bytes(cfg, cfg.n_rx)  # y in
                + cfg.n_subcarriers * cfg.n_rx * cfg.n_tx * _C16  # H in
                + _grid_bytes(cfg, cfg.n_tx * modem.bits_per_symbol // 2)
                # ^ LLRs out; x_hat / nv_eff / h_eff never leave L1
            ),
        )

    return RxStage("detect_demap_fused", "PE", apply, cycles)


def sic_demap_stage(cfg: ofdm.GridConfig, modem: ofdm.Modem,
                    precision: Optional[str] = None) -> RxStage:
    """Fused SIC equalize→demap (the MU-MIMO near-far receiver stage).

    One :mod:`repro.kernels.rx_fused` pass per (batch, subcarrier) tile:
    ``n_tx`` cancellation stages, each a shrinking in-register Gram/Gauss
    solve over the not-yet-cancelled stream suffix, followed by a hard
    re-modulation and residual subtraction that never leave the tile.
    Streams are cancelled in index order (the repo's MU-MIMO scenarios
    register ``user_power_db`` strongest-first).  ``precision`` behaves
    as in :func:`detect_demap_stage`.
    """

    def apply(state):
        h_est = state.get("h_hat", state.get("h_ls"))
        x_hat, nv_eff, llr = rx_fused.sic_detect_demap(
            state["y"], h_est, state["noise_var"], modem,
            precision=precision,
        )
        state["x_hat"], state["nv_eff"], state["llr"] = x_hat, nv_eff, llr
        return state

    def cycles():
        t, r = cfg.n_tx, cfg.n_rx
        lvl = 2 ** (modem.bits_per_symbol // 2)
        # shrinking gram+solve+rhs per cancellation stage (sizes t..1),
        # one stream demapped per stage, plus the hard-remod cancellation
        solve = sum(8.0 * (m * m * r + m ** 3 + m * r)
                    for m in range(1, t + 1))
        per_re = solve + t * lvl * 8.0 + (t - 1) * 8.0 * r
        flops = cfg.n_symbols * cfg.n_subcarriers * per_re
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.8),
            dma_cycles=pool.dma_cycles(
                _grid_bytes(cfg, cfg.n_rx)  # y in
                + cfg.n_subcarriers * cfg.n_rx * cfg.n_tx * _C16  # H in
                + _grid_bytes(cfg, cfg.n_tx * modem.bits_per_symbol // 2)
                # ^ LLRs out; residuals / x_hat / nv_eff stay in L1
            ),
        )

    return RxStage("sic_demap_fused", "PE", apply, cycles)


def detect_stage(cfg: ofdm.GridConfig, fused: bool = False,
                 modem: Optional[ofdm.Modem] = None,
                 precision: Optional[str] = None) -> RxStage:
    """MIMO-MMSE detection; ``fused=True`` (requires ``modem``) returns the
    combined :func:`detect_demap_stage` — the demap rides inside it, so
    builders must then skip :func:`demod_stage`."""
    if fused:
        assert modem is not None, "fused detect+demap needs the modem"
        return detect_demap_stage(cfg, modem, precision=precision)

    def apply(state):
        h_est = state.get("h_hat", state.get("h_ls"))
        b, n_sym, n_sc, n_rx = state["y"].shape
        yf = state["y"].reshape(b * n_sym, n_sc, n_rx)
        x_hat, nv_eff = classical.mimo_mmse_detect_ext(
            yf, _broadcast_h(h_est, n_sym), state["noise_var"]
        )
        state["x_hat"] = x_hat.reshape(b, n_sym, n_sc, cfg.n_tx)
        state["nv_eff"] = nv_eff.reshape(b, n_sym, n_sc, cfg.n_tx)
        return state

    def cycles():
        t, r = cfg.n_tx, cfg.n_rx
        per_re = 8.0 * (t * t * r + t ** 3 + t * r)  # gram+solve+rhs
        flops = cfg.n_symbols * cfg.n_subcarriers * per_re
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.59),
            dma_cycles=pool.dma_cycles(
                _grid_bytes(cfg, cfg.n_rx) + _grid_bytes(cfg, cfg.n_tx)
            ),
        )

    return RxStage("mmse_detect", "PE", apply, cycles)


def demod_stage(cfg: ofdm.GridConfig, modem: ofdm.Modem,
                precision: Optional[str] = None) -> RxStage:
    def apply(state):
        llr = modem.demod_llr(state["x_hat"], state["nv_eff"])
        if precision is not None and quant.is_quantized(precision):
            llr = quant.fake_quant_llr(llr, precision)
        state["llr"] = llr
        return state

    def cycles():
        lvl = 2 ** (modem.bits_per_symbol // 2)
        flops = (cfg.n_symbols * cfg.n_subcarriers * cfg.n_tx
                 * lvl * 8.0)
        return pool.BlockCycles(
            te_cycles=0.0,
            pe_cycles=pool.pe_cycles(flops, ipc=0.6),
            dma_cycles=pool.dma_cycles(
                _grid_bytes(cfg, cfg.n_tx * modem.bits_per_symbol // 2)
            ),
        )

    return RxStage("llr_demod", "PE", apply, cycles)


def decode_stage(scenario: LinkScenario, *, max_iters: int = 12,
                 alpha: float = 0.8,
                 precision: Optional[str] = None) -> RxStage:
    """CRC + LDPC decode of the slot's transport blocks (coded scenarios).

    Gathers the data-RE LLRs in the canonical codeword order, de-rate-
    matches (zero LLRs on the punctured tail) and runs the batched layered
    min-sum decoder (:mod:`repro.kernels.ldpc` — Pallas on TPU, jnp
    elsewhere), then CRC-checks the systematic part.  Adds
    ``info_bits_hat`` / ``crc_ok`` / ``decode_iters`` / ``cw_llr`` to the
    state.

    HARQ state rides in the slot: when the closed-loop runtime
    (:mod:`repro.serve.runtime`) stamps an ``rv`` array (B,) and a
    ``prior_llr`` buffer (B, C, n_mother) into the slot, de-rate-matching
    reads each slot's redundancy-version window and accumulates the prior
    soft bits before decoding — chase + incremental-redundancy combining
    inside the same compiled batch.  Slots without those keys decode
    exactly as before (RV0, no prior).

    Cycle model: the min-sum sweeps are PE (VPU) work — per iteration each
    edge costs ~8 ops over the z lanes, and the syndrome check ~2 — while
    the GF(2) CRC matrix product rides the TEs.  The LLR state is
    L1-resident across iterations, so DMA is one posterior-size round trip
    per codeword, not one per iteration.  The budget charges ``max_iters/2``
    iterations (layered decoding converges early at operating SNR; the
    serve report carries the measured count).
    """
    code = scenario.code
    assert code is not None, f"{scenario.name} has no channel code"
    n_cw = coding.codewords_per_slot(scenario)

    def apply(state):
        state.update(
            coding.decode_blocks(
                scenario, state["llr"], max_iters=max_iters, alpha=alpha,
                rv=state.get("rv"), prior_llr=state.get("prior_llr"),
                precision=precision,
            )
        )
        return state

    def cycles():
        n_edges = sum(len(e) for e in code.layers())
        iters_budget = max_iters / 2.0
        sweep_flops = n_cw * iters_budget * n_edges * code.z * 8.0
        syndrome_flops = n_cw * iters_budget * n_edges * code.z * 2.0
        crc_macs = n_cw * code.k_info * code.crc_bits
        return pool.BlockCycles(
            te_cycles=pool.te_cycles(crc_macs, utilization=0.67),
            pe_cycles=pool.pe_cycles(sweep_flops + syndrome_flops, ipc=0.7),
            dma_cycles=pool.dma_cycles(
                # LLRs in + posterior/bits out; the per-iteration state
                # (v, check messages) never leaves L1
                n_cw * code.n_mother * 4.0 + n_cw * code.k / 8.0
            ),
        )

    return RxStage("ldpc_decode", "PE", apply, cycles)


def llr_quant_stage(precision: str) -> RxStage:
    """Round-trip the LLR plane through the precision's grid (see
    :func:`repro.kernels.quant.fake_quant_llr`).  Appended after receivers
    that emit LLRs directly (DeepRx) so the decoder sees the same int8
    grid a quantized demapper would hand it.  Pure elementwise PE work;
    the grid never leaves L1, so no extra DMA is charged."""
    p = quant.resolve_precision(precision)

    def apply(state):
        state["llr"] = quant.fake_quant_llr(state["llr"], p)
        return state

    return RxStage(f"llr_quant@{p}", "PE", apply, None)


# -- neural stages ----------------------------------------------------------

def deeprx_stage(cfg: ofdm.GridConfig, modem: ofdm.Modem, params,
                 dcfg: models.DeepRxConfig, fused: bool = True) -> RxStage:
    union = jnp.any(ofdm.link_pilot_masks(cfg), axis=0)
    nb = modem.bits_per_symbol

    def apply(state):
        y = state["y"]  # (B, n_sym, n_sc, n_rx)
        b, n_sym, n_sc, n_rx = y.shape
        h_ls = state["h_ls"].reshape(b, 1, n_sc, -1)
        h_ls = jnp.broadcast_to(
            h_ls, (b, n_sym, n_sc, h_ls.shape[-1])
        )
        pm = jnp.broadcast_to(
            union[None, :, :, None].astype(jnp.float32),
            (b, n_sym, n_sc, 1),
        )
        nv = jnp.full((b, n_sym, n_sc, 1), state["noise_var"], jnp.float32)
        feats = jnp.concatenate(
            [jnp.real(y), jnp.imag(y), jnp.real(h_ls), jnp.imag(h_ls),
             pm, nv], axis=-1,
        ).astype(jnp.float32)
        llr = models.deeprx_apply(params, dcfg, feats, fused=fused)
        state["llr"] = llr.reshape(b, n_sym, n_sc, cfg.n_tx, nb)
        return state

    def cycles():
        grid = cfg.n_symbols * cfg.n_subcarriers
        c = dcfg.channels
        macs = grid * (9.0 * dcfg.in_features * c
                       + dcfg.blocks * 2 * 9.0 * c * c
                       + c * dcfg.bits_per_re)
        relu_elems = grid * c * (1 + 2 * dcfg.blocks)
        from repro.common.params import tree_size_bytes
        pbytes = tree_size_bytes(
            jax.tree.map(lambda x: x.astype(jnp.float16), params)
        )
        return pool.BlockCycles(
            te_cycles=pool.te_cycles(macs, utilization=0.67),
            pe_cycles=pool.pe_elem_cycles(relu_elems, "relu"),
            dma_cycles=pool.dma_cycles(
                pbytes + _grid_bytes(cfg, dcfg.in_features)
                + _grid_bytes(cfg, dcfg.bits_per_re)
            ),
        )

    return RxStage("deeprx", "TE", apply, cycles)


def cevit_che_stage(cfg: ofdm.GridConfig, params,
                    mcfg: models.CEViTConfig, fused: bool = True) -> RxStage:
    comb_tx = jnp.any(ofdm.link_pilot_masks(cfg), axis=1)  # (n_tx, n_sc)

    def apply(state):
        h_ls = state["h_ls"]  # (B, n_sc, n_rx, n_tx)
        b, n_sc, n_rx, n_tx = h_ls.shape
        pairs = jnp.moveaxis(h_ls, 1, -1).reshape(b * n_rx * n_tx, n_sc)
        flags = jnp.tile(comb_tx.astype(jnp.float32), (n_rx, 1))
        flags = jnp.tile(flags, (b, 1))  # (B*n_rx*n_tx, n_sc)
        nv = jnp.full(pairs.shape, state["noise_var"], jnp.float32)
        feats = jnp.stack(
            [jnp.real(pairs), jnp.imag(pairs), flags, nv], axis=-1
        ).astype(jnp.float32)
        h_hat = models.cevit_apply(params, mcfg, feats, fused=fused)
        h_hat = h_hat.reshape(b, n_rx, n_tx, n_sc)
        state["h_hat"] = jnp.moveaxis(h_hat, -1, 1)
        return state

    def cycles():
        n_tok = cfg.n_subcarriers // mcfg.patch
        pairs = cfg.n_rx * cfg.n_tx
        per_layer = pool.mha_block_cycles(
            mcfg.heads, n_tok, mcfg.d_model
        )
        mlp_macs = 2.0 * n_tok * mcfg.d_model * mcfg.d_ff
        pin = mcfg.patch * mcfg.in_features
        embed_macs = n_tok * pin * mcfg.d_model
        head_macs = n_tok * mcfg.d_model * mcfg.patch * 2
        ln_elems = mcfg.layers * 2 * n_tok * mcfg.d_model
        gelu_elems = mcfg.layers * n_tok * mcfg.d_ff
        one_pair = _sum_cycles(
            [per_layer] * mcfg.layers
            + [pool.BlockCycles(
                te_cycles=pool.te_cycles(
                    mcfg.layers * mlp_macs + embed_macs + head_macs,
                    utilization=0.67,
                ),
                pe_cycles=(pool.pe_elem_cycles(ln_elems, "layernorm")
                           + pool.pe_elem_cycles(gelu_elems, "relu")),
                dma_cycles=pool.dma_cycles(
                    2 * cfg.n_subcarriers * _C16
                ),
            )]
        )
        return pool.BlockCycles(
            te_cycles=pairs * one_pair.te_cycles,
            pe_cycles=pairs * one_pair.pe_cycles,
            dma_cycles=pairs * one_pair.dma_cycles,
        )

    return RxStage("cevit_che", "TE", apply, cycles)


# ---------------------------------------------------------------------------
# Pipeline builders — the three receivers behind one API
# ---------------------------------------------------------------------------

def _precision_tag(precision: str) -> str:
    return f"@{precision}" if quant.is_quantized(precision) else ""


def build_classical(scenario: LinkScenario, *, mmse_smooth: bool = True,
                    fused: bool = False, sic: bool = False,
                    precision: Optional[str] = None,
                    **_) -> ReceiverPipeline:
    """CFFT -> LS CHE [-> Wiener CHE] -> MIMO-MMSE detect -> LLR demod
    [-> CRC+LDPC decode].

    ``fused=True`` serves the chain through the fused classical-receiver
    kernels (:mod:`repro.kernels.rx_fused`): LS CHE as one interp GEMM and
    detect+demap as one pass (Pallas on TPU, the same fused math as one
    XLA-fused function elsewhere).  Coded scenarios terminate in the
    decoder (bits out, BLER-scored) instead of raw LLRs.

    ``precision="int8"|"fp8"`` serves the LLR plane on the quantized grid
    and runs the int8 layered min-sum decoder; the pipeline's energy
    report prices the datapath at that precision.

    ``sic=True`` replaces the joint-LMMSE detect+demap with the fused
    successive-interference-cancellation stage
    (:func:`sic_demap_stage`) — the MU-MIMO near-far receiver.  SIC is
    always served fused (the cancellation residuals live in-tile);
    ``fused`` then only controls the LS-CHE path.
    """
    p = quant.resolve_precision(precision)
    cfg, modem = scenario.grid, scenario.modem
    stages = [cfft_stage(cfg), ls_che_stage(cfg, fused=fused)]
    if mmse_smooth:
        stages.append(mmse_che_stage(cfg))
    if sic:
        stages.append(sic_demap_stage(cfg, modem, precision=p))
    elif fused:
        stages.append(detect_stage(cfg, fused=True, modem=modem,
                                   precision=p))
    else:
        stages += [detect_stage(cfg), demod_stage(cfg, modem, precision=p)]
    if scenario.code is not None:
        stages.append(decode_stage(scenario, precision=p))
    tag = ("+sic" if sic else "") + ("+fused" if fused else "")
    return ReceiverPipeline(
        f"classical{tag}{_precision_tag(p)}/{scenario.name}",
        stages, scenario, precision=p,
    )


def build_deeprx(scenario: LinkScenario, *, params=None, channels: int = 32,
                 blocks: int = 2, fused: bool = True,
                 seed: int = 0, precision: Optional[str] = None,
                 **_) -> ReceiverPipeline:
    """CFFT -> LS CHE -> DeepRx conv receiver (grid features -> LLRs).

    Quantized precisions fake-quant the network's output LLR plane onto
    the int8 grid (the conv body stays at its trained precision; the
    decoder and energy model see the quantized datapath).
    """
    p = quant.resolve_precision(precision)
    cfg, modem = scenario.grid, scenario.modem
    dcfg = models.DeepRxConfig(
        channels=channels, blocks=blocks,
        bits_per_re=cfg.n_tx * modem.bits_per_symbol,
        in_features=2 * cfg.n_rx + 2 * cfg.n_rx * cfg.n_tx + 2,
    )
    if params is None:
        params = models.init_deeprx(jax.random.PRNGKey(seed), dcfg)
    stages = [
        cfft_stage(cfg), ls_che_stage(cfg),
        deeprx_stage(cfg, modem, params, dcfg, fused=fused),
    ]
    if quant.is_quantized(p):
        stages.append(llr_quant_stage(p))
    if scenario.code is not None:
        stages.append(decode_stage(scenario, precision=p))
    return ReceiverPipeline(
        f"deeprx{_precision_tag(p)}/{scenario.name}", stages, scenario,
        params=params, precision=p,
    )


def build_cevit(scenario: LinkScenario, *, params=None, d_model: int = 64,
                heads: int = 4, layers: int = 2, d_ff: int = 128,
                patch: int = 4, fused: bool = True, fused_rx: bool = False,
                seed: int = 0, precision: Optional[str] = None,
                **_) -> ReceiverPipeline:
    """CFFT -> LS CHE -> CE-ViT CHE -> MIMO-MMSE detect -> LLR demod.

    ``fused`` routes the neural CHE through the Pallas model kernels;
    ``fused_rx`` additionally serves the classical detect+demap tail
    through the fused receiver kernel.
    """
    p = quant.resolve_precision(precision)
    cfg, modem = scenario.grid, scenario.modem
    mcfg = models.CEViTConfig(
        d_model=d_model, heads=heads, layers=layers, d_ff=d_ff, patch=patch
    )
    if params is None:
        params = models.init_cevit(jax.random.PRNGKey(seed), mcfg)
    stages = [
        cfft_stage(cfg), ls_che_stage(cfg),
        cevit_che_stage(cfg, params, mcfg, fused=fused),
    ]
    if fused_rx:
        stages.append(detect_stage(cfg, fused=True, modem=modem,
                                   precision=p))
    else:
        stages += [detect_stage(cfg), demod_stage(cfg, modem, precision=p)]
    if scenario.code is not None:
        stages.append(decode_stage(scenario, precision=p))
    return ReceiverPipeline(
        f"cevit{_precision_tag(p)}/{scenario.name}", stages, scenario,
        params=params, precision=p,
    )


PIPELINE_BUILDERS: dict[str, Callable[..., ReceiverPipeline]] = {
    "classical": build_classical,
    "deeprx": build_deeprx,
    "cevit": build_cevit,
}


def build_pipeline(kind: str, scenario: LinkScenario,
                   **kw) -> ReceiverPipeline:
    if kind not in PIPELINE_BUILDERS:
        raise KeyError(
            f"unknown receiver {kind!r}; have {sorted(PIPELINE_BUILDERS)}"
        )
    return PIPELINE_BUILDERS[kind](scenario, **kw)
