"""OFDM uplink simulation substrate (paper §II domain).

Resource grid, gray-coded square-QAM modems (QPSK/16/64-QAM), Rayleigh TDL
channel with exponential power delay profile (optionally time-varying for
Doppler scenarios), AWGN — everything needed to generate synthetic uplink
slots for the classical chain and the neural receivers, SISO through MIMO.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GridConfig:
    n_subcarriers: int = 512  # frequency bins (REs per symbol)
    n_symbols: int = 14  # OFDM symbols per slot (one TTI)
    pilot_stride: int = 4  # pilot every k-th subcarrier
    pilot_symbols: tuple = (2, 11)  # DMRS symbol positions
    n_tx: int = 1
    n_rx: int = 1
    fft_size: int = 512
    n_taps: int = 8  # channel delay taps
    delay_spread: float = 2.0  # exponential PDP decay (in taps)


# ---------------------------------------------------------------------------
# Constellation-parameterized modem (gray-coded square QAM)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Modem:
    """Gray-coded square-QAM modem.

    ``levels[j]`` is the per-axis amplitude for the axis-bit integer ``j``
    (MSB first), so adjacent constellation points differ in one bit.  Bits
    are laid out (..., bits_per_symbol) with the first half on the real
    axis, the second half on the imaginary axis.
    """
    name: str
    bits_per_symbol: int
    levels: tuple  # indexed by the bit-int of one axis
    norm: float  # mean symbol energy of the un-normalized grid

    @property
    def bits_per_axis(self) -> int:
        return self.bits_per_symbol // 2

    def mod(self, bits: jax.Array) -> jax.Array:
        """bits (..., bits_per_symbol) -> unit-power complex symbols."""
        nb = self.bits_per_axis
        lv = jnp.asarray(self.levels, jnp.float32)
        w = (2 ** jnp.arange(nb - 1, -1, -1)).astype(jnp.int32)
        idx_re = jnp.sum(bits[..., :nb].astype(jnp.int32) * w, axis=-1)
        idx_im = jnp.sum(bits[..., nb:].astype(jnp.int32) * w, axis=-1)
        return (lv[idx_re] + 1j * lv[idx_im]) / jnp.sqrt(self.norm)

    def demod_llr(self, y: jax.Array, noise_var: jax.Array) -> jax.Array:
        """Max-log LLRs. y (...,) complex -> (..., bits_per_symbol).

        Convention: llr = log P(b=1)/P(b=0); hard decision is ``llr > 0``.
        ``noise_var`` broadcasts against ``y`` (scalar or per-element).
        """
        nb = self.bits_per_axis
        lv = jnp.asarray(self.levels, jnp.float32)
        s = jnp.sqrt(self.norm)
        nv = jnp.maximum(
            jnp.broadcast_to(noise_var, y.shape) * self.norm, 1e-6
        )
        bit_of = np.array(
            [[(j >> (nb - 1 - p)) & 1 for j in range(len(self.levels))]
             for p in range(nb)], dtype=bool,
        )  # (nb, L): bit p of the level index

        def axis_llrs(u):
            d = (u[..., None] - lv) ** 2  # (..., L)
            out = []
            for p in range(nb):
                one = jnp.asarray(bit_of[p])
                d0 = jnp.min(jnp.where(one, jnp.inf, d), axis=-1)
                d1 = jnp.min(jnp.where(one, d, jnp.inf), axis=-1)
                out.append(d0 - d1)
            return out

        llrs = axis_llrs(jnp.real(y) * s) + axis_llrs(jnp.imag(y) * s)
        return jnp.stack(llrs, axis=-1) / nv[..., None]


_MODEMS = {
    "qpsk": Modem("qpsk", 2, (-1.0, 1.0), 2.0),
    "qam16": Modem("qam16", 4, (-3.0, -1.0, 3.0, 1.0), 10.0),
    "qam64": Modem(
        "qam64", 6, (-7.0, -5.0, -1.0, -3.0, 7.0, 5.0, 1.0, 3.0), 42.0
    ),
    # levels[gray(k)] = 2k - 15: binary-reflected gray over 16 amplitudes,
    # same construction as qam16/qam64; norm = 2 * mean(levels^2) = 170
    "qam256": Modem(
        "qam256", 8,
        (-15.0, -13.0, -9.0, -11.0, -1.0, -3.0, -7.0, -5.0,
         15.0, 13.0, 9.0, 11.0, 1.0, 3.0, 7.0, 5.0), 170.0
    ),
}
_ORDER_TO_NAME = {4: "qpsk", 16: "qam16", 64: "qam64", 256: "qam256"}


def make_modem(modulation) -> Modem:
    """Look up a modem by name ("qpsk"/"qam16"/"qam64"/"qam256") or order
    (4/16/64/256)."""
    if isinstance(modulation, Modem):
        return modulation
    if isinstance(modulation, int):
        modulation = _ORDER_TO_NAME[modulation]
    return _MODEMS[modulation]


def qam16_mod(bits: jax.Array) -> jax.Array:
    """bits: (..., 4) -> complex symbol (gray-coded 16-QAM, unit power)."""
    return _MODEMS["qam16"].mod(bits)


def qam16_demod_llr(y: jax.Array, noise_var: jax.Array) -> jax.Array:
    """Max-log LLRs for gray 16-QAM. y: (...,) complex -> (..., 4)."""
    return _MODEMS["qam16"].demod_llr(y, noise_var)


def tdl_channel(key: jax.Array, cfg: GridConfig, batch: int) -> jax.Array:
    """Rayleigh TDL -> frequency response H (batch, n_rx, n_tx, n_sc)."""
    pdp = jnp.exp(-jnp.arange(cfg.n_taps) / cfg.delay_spread)
    pdp = pdp / jnp.sum(pdp)
    kr, ki = jax.random.split(key)
    shape = (batch, cfg.n_rx, cfg.n_tx, cfg.n_taps)
    taps = (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape))
    taps = taps * jnp.sqrt(pdp / 2.0)
    # frequency response: FFT of the tap vector zero-padded to fft_size
    h = jnp.fft.fft(taps, n=cfg.fft_size, axis=-1)[..., : cfg.n_subcarriers]
    return h  # (B, n_rx, n_tx, n_sc)


def pilot_mask(cfg: GridConfig) -> jax.Array:
    """(n_symbols, n_subcarriers) bool mask of pilot REs."""
    m = jnp.zeros((cfg.n_symbols, cfg.n_subcarriers), bool)
    sc = jnp.arange(cfg.n_subcarriers) % cfg.pilot_stride == 0
    for sym in cfg.pilot_symbols:
        m = m.at[sym].set(sc)
    return m


def make_slot(key: jax.Array, cfg: GridConfig, batch: int, snr_db: float):
    """Simulate one uplink slot (SISO path of the grid).

    Returns dict(y, x, h, bits, pilots, noise_var):
      y (B, n_sym, n_sc) received grid, x transmitted symbols,
      h (B, n_sc) channel (flat in time within the slot), bits (B, n_sym,
      n_sc, 4).
    """
    kb, kc, kn = jax.random.split(key, 3)
    bits = jax.random.bernoulli(
        kb, 0.5, (batch, cfg.n_symbols, cfg.n_subcarriers, 4)
    ).astype(jnp.int32)
    x = qam16_mod(bits)  # (B, n_sym, n_sc)
    h = tdl_channel(kc, cfg, batch)[:, 0, 0, :]  # (B, n_sc)
    # pilots: known unit-power QPSK on the pilot mask
    pm = pilot_mask(cfg)
    pilots = jnp.exp(
        1j * (jnp.pi / 4 + jnp.pi / 2 * (jnp.arange(cfg.n_subcarriers) % 4))
    )
    x = jnp.where(pm[None], pilots[None, None, :], x)
    snr = 10.0 ** (snr_db / 10.0)
    noise_var = 1.0 / snr
    kn1, kn2 = jax.random.split(kn)
    noise = (jax.random.normal(kn1, x.shape) + 1j * jax.random.normal(kn2, x.shape))
    noise = noise * jnp.sqrt(noise_var / 2.0)
    y = x * h[:, None, :] + noise
    return {
        "y": y, "x": x, "h": h, "bits": bits,
        "pilots": pilots, "pilot_mask": pm,
        "noise_var": jnp.asarray(noise_var, jnp.float32),
    }


def tdl_channel_time_varying(
    key: jax.Array, cfg: GridConfig, batch: int, n_steps: int, rho: float
) -> jax.Array:
    """Gauss-Markov time-varying Rayleigh TDL.

    Per-symbol tap correlation ``rho`` (Jakes' J0(2 pi fd T) in the AR(1)
    approximation); rho=1 reduces to a block-fading channel.  Returns the
    frequency response (batch, n_steps, n_rx, n_tx, n_sc).
    """
    pdp = jnp.exp(-jnp.arange(cfg.n_taps) / cfg.delay_spread)
    pdp = pdp / jnp.sum(pdp)
    shape = (batch, cfg.n_rx, cfg.n_tx, cfg.n_taps)

    def cnormal(k, shp):
        kr, ki = jax.random.split(k)
        return jax.random.normal(kr, shp) + 1j * jax.random.normal(ki, shp)

    k0, kw = jax.random.split(key)
    taps0 = cnormal(k0, shape) * jnp.sqrt(pdp / 2.0)
    innov = cnormal(kw, (n_steps - 1,) + shape) * jnp.sqrt(pdp / 2.0)

    def step(carry, w):
        nxt = rho * carry + jnp.sqrt(1.0 - rho**2) * w
        return nxt, nxt

    _, rest = jax.lax.scan(step, taps0, innov)
    taps = jnp.concatenate([taps0[None], rest], axis=0)  # (T, B, r, t, taps)
    taps = jnp.moveaxis(taps, 0, 1)  # (B, T, r, t, taps)
    h = jnp.fft.fft(taps, n=cfg.fft_size, axis=-1)[..., : cfg.n_subcarriers]
    return h


def pilot_sequence(cfg: GridConfig) -> jax.Array:
    """(n_sc,) known unit-power QPSK DMRS sequence."""
    return jnp.exp(
        1j * (jnp.pi / 4 + jnp.pi / 2 * (jnp.arange(cfg.n_subcarriers) % 4))
    )


def link_pilot_masks_np(cfg: GridConfig) -> np.ndarray:
    """Numpy twin of :func:`link_pilot_masks` for static (trace-time)
    geometry: codeword/RE counting must not stage jnp ops under jit."""
    spacing = cfg.pilot_stride * cfg.n_tx
    sc = np.arange(cfg.n_subcarriers)
    masks = np.zeros((cfg.n_tx, cfg.n_symbols, cfg.n_subcarriers), bool)
    for t in range(cfg.n_tx):
        comb = sc % spacing == t * cfg.pilot_stride
        for sym in cfg.pilot_symbols:
            masks[t, sym] = comb
    return masks


def link_pilot_masks(cfg: GridConfig) -> jax.Array:
    """(n_tx, n_symbols, n_subcarriers) bool: staggered per-tx DMRS combs.

    Tx ``t`` transmits pilots on subcarriers ``sc % (stride * n_tx) ==
    t * stride`` of the pilot symbols; on another tx's comb it is silent,
    so per-(rx, tx) LS estimates are interference-free.
    """
    return jnp.asarray(link_pilot_masks_np(cfg))


def make_link_slot(
    key: jax.Array,
    cfg: GridConfig,
    modem: Modem,
    batch: int,
    snr_db: float,
    doppler_rho: float = 1.0,
    bits=None,
    interferer_db: tuple = (),
    user_power_db=None,
):
    """Simulate one uplink slot of the unified link schema (SISO..MIMO).

    Returns dict with batched arrays
      y_time (B, n_sym, n_sc, n_rx)  time-domain input of the CFFT stage,
      y      (B, n_sym, n_sc, n_rx)  received frequency grid,
      x      (B, n_sym, n_sc, n_tx)  transmitted symbols (pilots embedded),
      h      (B, T, n_sc, n_rx, n_tx) channel (T=1 static, T=n_sym Doppler),
      bits   (B, n_sym, n_sc, n_tx, bits_per_symbol),
    and unbatched side info: noise_var (scalar), pilot_seq (n_sc,),
    pilot_masks (n_tx, n_sym, n_sc), data_mask (n_sym, n_sc).

    ``bits`` injects pre-drawn payload bits of that grid shape (the coded
    path in :mod:`repro.phy.coding` lays codewords onto the data REs);
    None draws i.i.d. uncoded bits.

    ``user_power_db`` (len n_tx) applies a per-stream receive-power
    offset — the MU-MIMO near-far profile when each tx layer is a
    different user.  The gain is folded into the stored channel (pilots
    ride it too), so channel estimation and detection see the *effective*
    per-user channel and stay oracle-consistent.

    ``interferer_db`` adds one co-channel interferer per entry at that
    power (dB relative to a 0 dB user): each draws an independent TDL
    channel (aging with the same ``doppler_rho``) and transmits random
    QPSK on the whole grid — DMRS REs included, so interference corrupts
    channel estimates exactly as a neighboring cell would.  The stored
    ``noise_var`` is thermal + total mean interference power per rx
    antenna (the interference-as-noise operating point the MMSE
    regularizer and the demapper should be told about).
    """
    nb = modem.bits_per_symbol
    if interferer_db:
        kb, kc, kn, ki = jax.random.split(key, 4)
    else:
        kb, kc, kn = jax.random.split(key, 3)
    if bits is None:
        bits = jax.random.bernoulli(
            kb, 0.5, (batch, cfg.n_symbols, cfg.n_subcarriers, cfg.n_tx, nb)
        ).astype(jnp.int32)
    x = modem.mod(bits)  # (B, n_sym, n_sc, n_tx)

    pm_tx = link_pilot_masks(cfg)  # (n_tx, n_sym, n_sc)
    union = jnp.any(pm_tx, axis=0)  # (n_sym, n_sc)
    seq = pilot_sequence(cfg)
    pm_grid = jnp.moveaxis(pm_tx, 0, -1)  # (n_sym, n_sc, n_tx)
    x = jnp.where(
        pm_grid[None], seq[None, None, :, None],
        jnp.where(union[None, ..., None], 0.0, x),
    )

    if doppler_rho < 1.0:
        h = tdl_channel_time_varying(
            kc, cfg, batch, cfg.n_symbols, doppler_rho
        )  # (B, n_sym, n_rx, n_tx, n_sc)
    else:
        h = tdl_channel(kc, cfg, batch)[:, None]  # (B, 1, n_rx, n_tx, n_sc)
    h = jnp.moveaxis(h, -1, 2)  # (B, T, n_sc, n_rx, n_tx)
    if user_power_db is not None:
        assert len(user_power_db) == cfg.n_tx, (
            f"user_power_db needs one entry per tx stream "
            f"({len(user_power_db)} != {cfg.n_tx})"
        )
        gains = jnp.asarray(
            [10.0 ** (p / 20.0) for p in user_power_db], jnp.float32
        )
        h = h * gains  # effective per-user channel (pilots included)

    hb = jnp.broadcast_to(
        h, (batch, cfg.n_symbols) + h.shape[2:]
    ) if h.shape[1] == 1 else h
    y = jnp.einsum("bmsrt,bmst->bmsr", hb, x)
    snr = 10.0 ** (snr_db / 10.0)
    if interferer_db:
        icfg = dataclasses.replace(cfg, n_tx=1)
        for p_db, k_i in zip(interferer_db,
                             jax.random.split(ki, len(interferer_db))):
            kch, ksym = jax.random.split(k_i)
            if doppler_rho < 1.0:
                hi = tdl_channel_time_varying(
                    kch, icfg, batch, cfg.n_symbols, doppler_rho
                )
            else:
                hi = tdl_channel(kch, icfg, batch)[:, None]
            hi = jnp.moveaxis(hi, -1, 2)  # (B, T, n_sc, n_rx, 1)
            hib = jnp.broadcast_to(
                hi, (batch, cfg.n_symbols) + hi.shape[2:]
            ) if hi.shape[1] == 1 else hi
            # unit-power QPSK on every RE of the co-channel grid
            qi = jax.random.randint(
                ksym, (batch, cfg.n_symbols, cfg.n_subcarriers), 0, 4
            )
            si = jnp.exp(1j * (jnp.pi / 4 + jnp.pi / 2 * qi))
            amp = 10.0 ** (p_db / 20.0)
            y = y + amp * hib[..., 0] * si[..., None]
    noise_var = noise_variance(cfg.n_tx, snr_db, interferer_db)
    kn1, kn2 = jax.random.split(kn)
    noise = jax.random.normal(kn1, y.shape) + 1j * jax.random.normal(
        kn2, y.shape
    )
    thermal_var = cfg.n_tx / snr
    y = y + noise * jnp.sqrt(thermal_var / 2.0)
    y_time = jnp.fft.ifft(y, axis=2)
    return {
        "y_time": y_time, "y": y, "x": x, "h": h, "bits": bits,
        "noise_var": jnp.asarray(noise_var, jnp.float32),
        "pilot_seq": seq, "pilot_masks": pm_tx, "data_mask": ~union,
    }


def noise_variance(n_tx: int, snr_db, interferer_db: tuple = ()):
    """A link slot's ``noise_var`` side info: thermal noise ``n_tx / snr``
    plus each co-channel interferer's mean power per receive antenna.
    Host floats in, a host float out (:attr:`LinkScenario.noise_var`);
    traced in, traced out (:func:`make_link_slot`)."""
    noise_var = n_tx / 10.0 ** (snr_db / 10.0)
    if interferer_db:
        noise_var = noise_var + sum(
            10.0 ** (p / 10.0) for p in interferer_db
        )
    return noise_var


def make_mimo_slot(key: jax.Array, cfg: GridConfig, batch: int, snr_db: float):
    """MIMO flat-per-subcarrier slot for MMSE detection benchmarks.

    Returns y (B, n_sc, n_rx), H (B, n_sc, n_rx, n_tx), x (B, n_sc, n_tx).
    """
    kb, kc, kn = jax.random.split(key, 3)
    bits = jax.random.bernoulli(
        kb, 0.5, (batch, cfg.n_subcarriers, cfg.n_tx, 4)
    ).astype(jnp.int32)
    x = qam16_mod(bits)  # (B, n_sc, n_tx)
    h = tdl_channel(kc, cfg, batch)  # (B, n_rx, n_tx, n_sc)
    h = jnp.moveaxis(h, -1, 1)  # (B, n_sc, n_rx, n_tx)
    snr = 10.0 ** (snr_db / 10.0)
    noise_var = cfg.n_tx / snr
    kn1, kn2 = jax.random.split(kn)
    nshape = (batch, cfg.n_subcarriers, cfg.n_rx)
    noise = (jax.random.normal(kn1, nshape) + 1j * jax.random.normal(kn2, nshape))
    noise = noise * jnp.sqrt(noise_var / 2.0)
    y = jnp.einsum("bsrt,bst->bsr", h, x) + noise
    return {
        "y": y, "h": h, "x": x, "bits": bits,
        "noise_var": jnp.asarray(noise_var, jnp.float32),
    }
