"""Channel-coding chain (paper §II: the TTI budget covers *coded* links).

The AI-native PHY workloads the paper provisions for are coded: the
sub-msec slot deadline includes CRC + LDPC decode, and RAN operators
provision against BLER, not raw LLR quality.  This module supplies the
transmit/receive coding chain around the detector pipeline:

* **CRC** attach/check — CRC is linear over GF(2), so both directions are
  a single bit-matrix product mod 2 against a precomputed generator
  matrix (tensor work, no shift registers at runtime).
* **LDPC encode** — a 5G-style *base-graph-lite* quasi-cyclic code: a
  small base graph ``(m_b x n_b)`` lifted by circulant size ``z``, with a
  dual-diagonal parity part so encoding is one sparse XOR-accumulate
  (``cumsum mod 2`` over block rows) instead of a dense generator.
* **Rate matching** — the mother codeword sits in a circular buffer and
  each transmission reads ``e_bits`` starting at a redundancy-version
  (RV) offset: RV0 is the systematic bits plus the leading parity blocks,
  higher RVs start deeper into the parity (incremental redundancy);
  ``derate_match`` scatters the received LLRs back to their mother-code
  positions (zero LLRs on untransmitted bits) and **accumulates** an
  optional prior buffer, so HARQ retransmissions combine soft information
  across rounds (chase combining when the windows overlap, IR where the
  RVs bring fresh parity).
* **Coded slot generation** — :func:`make_coded_slot` encodes per-slot
  transport blocks and maps the codeword bits onto the OFDM grid's data
  REs in a fixed canonical order, so :func:`coded_llrs` (used by the
  receiver's decode stage) can gather them back.

The decoder itself lives in :mod:`repro.kernels.ldpc` (a batched layered
normalized-min-sum Pallas kernel with a shared jnp path); this module owns
the static code structure both sides agree on.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.phy import ofdm

# CRC-16-CCITT generator polynomial (x^16 + x^12 + x^5 + 1), MSB-first
CRC16_POLY = 0x1021
CRC_BITS = 16


# ---------------------------------------------------------------------------
# CRC over GF(2) as a matrix product
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def crc_matrix(k_info: int, poly: int = CRC16_POLY,
               n_crc: int = CRC_BITS) -> np.ndarray:
    """(k_info, n_crc) binary matrix M with crc(bits) = bits @ M mod 2.

    Row i is the CRC of the unit message e_i (zero-init, no xor-out), so
    linearity gives the CRC of any message as the XOR of its rows.
    """
    m = np.zeros((k_info, n_crc), np.int8)
    for i in range(k_info):
        reg = 0
        for j in range(k_info):
            bit = 1 if j == i else 0
            top = (reg >> (n_crc - 1)) & 1
            reg = ((reg << 1) & ((1 << n_crc) - 1)) | 0
            if top ^ bit:
                reg ^= poly
        m[i] = [(reg >> (n_crc - 1 - b)) & 1 for b in range(n_crc)]
    return m


def crc_attach(info: jax.Array, n_crc: int = CRC_BITS) -> jax.Array:
    """info (..., k_info) int bits -> (..., k_info + n_crc) with CRC."""
    m = jnp.asarray(crc_matrix(info.shape[-1], n_crc=n_crc), jnp.int32)
    crc = jnp.mod(info.astype(jnp.int32) @ m, 2)
    return jnp.concatenate([info.astype(jnp.int32), crc], axis=-1)


def crc_check(bits: jax.Array, n_crc: int = CRC_BITS) -> jax.Array:
    """bits (..., k_info + n_crc) -> (...,) bool, True when the CRC holds."""
    info, crc = bits[..., :-n_crc], bits[..., -n_crc:]
    m = jnp.asarray(crc_matrix(info.shape[-1], n_crc=n_crc), jnp.int32)
    expect = jnp.mod(info.astype(jnp.int32) @ m, 2)
    return jnp.all(expect == crc.astype(jnp.int32), axis=-1)


# ---------------------------------------------------------------------------
# Base-graph-lite QC-LDPC code
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CodeConfig:
    """One rate point of the base-graph-lite QC-LDPC code.

    The mother code has ``k_b`` systematic and ``m_b`` parity block
    columns, lifted by circulant size ``z``; ``info_edges[j]`` lists the
    ``(block_col, shift)`` circulants of block row ``j``'s systematic
    part, and the parity part is dual-diagonal with identity circulants
    (``p_j = p_{j-1} XOR s_j``).  Rate matching transmits the systematic
    bits plus the first ``p_tx_b`` parity blocks.

    Frozen + tuple-valued so a config can sit inside a
    :class:`~repro.phy.scenarios.LinkScenario` and take part in the
    mesh engine's shape-group key.
    """
    name: str
    z: int
    k_b: int
    m_b: int
    p_tx_b: int
    info_edges: tuple  # per block-row: ((col, shift), ...)
    crc_bits: int = CRC_BITS

    @property
    def n_b(self) -> int:
        return self.k_b + self.m_b

    @property
    def k(self) -> int:
        """Systematic bits per codeword (CRC included)."""
        return self.k_b * self.z

    @property
    def k_info(self) -> int:
        """Payload bits per codeword (CRC excluded)."""
        return self.k - self.crc_bits

    @property
    def n_mother(self) -> int:
        return self.n_b * self.z

    @property
    def e_bits(self) -> int:
        """Transmitted (rate-matched) bits per codeword."""
        return (self.k_b + self.p_tx_b) * self.z

    @property
    def rate(self) -> float:
        return self.k / self.e_bits

    def layers(self) -> tuple:
        """Per block-row edge lists ((col, shift), ...) including the
        dual-diagonal parity circulants — the layered decoder's schedule.
        Within a block row every block column appears at most once, so
        the ``z`` lifted rows of a layer are independent (vectorizable)."""
        out = []
        for j in range(self.m_b):
            edges = list(self.info_edges[j])
            if j > 0:
                edges.append((self.k_b + j - 1, 0))
            edges.append((self.k_b + j, 0))
            out.append(tuple(edges))
        return tuple(out)

    def punctured_blocks(self) -> tuple:
        """Block columns whose bits are never transmitted (zero LLRs)."""
        return tuple(range(self.k_b + self.p_tx_b, self.n_b))


def _make_info_edges(k_b: int, m_b: int, z: int, col_degree: int,
                     seed: int) -> tuple:
    """Deterministic pseudo-random protograph for the systematic part.

    Each info block column lands in ``col_degree`` distinct block rows
    (spread round-robin so row degrees stay balanced) with a random
    circulant shift.  No (row, col) pair repeats, keeping the z lifted
    rows of each layer independent.
    """
    rng = np.random.default_rng(seed)
    rows_of = [[] for _ in range(m_b)]
    for c in range(k_b):
        # least-loaded rows first, tie-broken randomly -> balanced degrees
        order = sorted(range(m_b),
                       key=lambda r: (len(rows_of[r]), rng.random()))
        for r in order[:col_degree]:
            rows_of[r].append((c, int(rng.integers(z))))
    return tuple(tuple(sorted(edges)) for edges in rows_of)


@functools.lru_cache(maxsize=None)
def make_code(rate: str = "r12", z: int = 32, k_b: int = 12,
              col_degree: int = 3, seed: int = 7) -> CodeConfig:
    """Build one rate point of the base-graph-lite family.

    Like 5G's two base graphs, each rate point picks a mother geometry
    and a rate-matching depth: ``"r12"`` transmits the full rate-1/2
    mother (``m_b = k_b``); ``"r34"`` starts from a rate-2/3 mother
    (``m_b = k_b/2``) and punctures its last two parity blocks, so the
    decoder always sees the whole mother graph with the punctured tail
    entering as zero LLRs.
    """
    m_b, p_tx = {
        "r12": (k_b, k_b),
        "r34": (k_b // 2, k_b // 3),
    }[rate]
    assert 0 < p_tx <= m_b, (rate, p_tx, m_b)
    edges = _make_info_edges(k_b, m_b, z, col_degree, seed)
    return CodeConfig(
        name=f"bg-lite-{rate}-z{z}", z=z, k_b=k_b, m_b=m_b, p_tx_b=p_tx,
        info_edges=edges,
    )


def dense_parity_matrix(code: CodeConfig) -> np.ndarray:
    """Expand the lifted graph to the dense (m_b*z, n_b*z) binary H —
    test/oracle helper, never used on the hot path."""
    z = code.z
    h = np.zeros((code.m_b * z, code.n_b * z), np.int8)
    for j, edges in enumerate(code.layers()):
        for c, s in edges:
            for r in range(z):
                h[j * z + r, c * z + (r + s) % z] = 1
    return h


# ---------------------------------------------------------------------------
# Encode / rate matching
# ---------------------------------------------------------------------------

def _rot(u: jax.Array, s: int) -> jax.Array:
    """Apply the shift-``s`` circulant: row r of the block picks bit
    (r + s) mod z of the variable block."""
    return jnp.roll(u, -s, axis=-1)


def encode(code: CodeConfig, bits: jax.Array) -> jax.Array:
    """Systematic QC-LDPC encode.  bits (..., k) -> codeword (..., n_mother).

    The dual-diagonal parity part makes encoding a prefix-XOR: block row
    j's systematic syndrome is s_j, and p_j = p_{j-1} XOR s_j, i.e. the
    cumulative XOR of the syndromes — one cumsum mod 2, no dense algebra.
    """
    assert bits.shape[-1] == code.k, (bits.shape, code.k)
    u = bits.reshape(bits.shape[:-1] + (code.k_b, code.z)).astype(jnp.int32)
    synd = []
    for edges in code.info_edges:
        s = jnp.zeros(u.shape[:-2] + (code.z,), jnp.int32)
        for c, sh in edges:
            s = s + _rot(u[..., c, :], sh)
        synd.append(s)
    s = jnp.stack(synd, axis=-2)  # (..., m_b, z)
    p = jnp.mod(jnp.cumsum(s, axis=-2), 2)
    cw = jnp.concatenate([u, p], axis=-2)
    return cw.reshape(bits.shape[:-1] + (code.n_mother,))


N_RV = 4  # redundancy versions cycling the circular buffer (5G-style)


def rv_offset(code: CodeConfig, rv):
    """Start offset (in mother-code bits) of redundancy version ``rv``.

    The mother codeword is a circular buffer; RV ``r`` transmits the
    ``e_bits`` window starting at block column ``r * n_b / 4`` (rounded
    down to a whole lifted block so circulant structure is preserved).
    Accepts a python int or an int array (per-codeword RVs).
    """
    return ((rv % N_RV) * code.n_b) // N_RV * code.z


def rate_match(code: CodeConfig, cw: jax.Array, rv=0) -> jax.Array:
    """codeword (..., n_mother) -> transmitted bits (..., e_bits): the
    circular-buffer window starting at :func:`rv_offset`.  RV0 is the
    systematic part + leading parity blocks (tail punctured).  ``rv`` may
    be a python int (static window) or a traced int scalar (one compiled
    generator serves every retransmission's RV)."""
    if isinstance(rv, (int, np.integer)):
        off = int(rv_offset(code, rv))
        if off == 0:
            return cw[..., : code.e_bits]
    else:
        off = rv_offset(code, jnp.asarray(rv, jnp.int32))
    return jnp.roll(cw, -off, axis=-1)[..., : code.e_bits]


def derate_match(code: CodeConfig, llr_e: jax.Array, rv=None,
                 prior: Optional[jax.Array] = None) -> jax.Array:
    """Received LLRs (..., e_bits) -> mother-code LLRs (..., n_mother).

    Scatters the transmitted window back to its circular-buffer positions
    (untransmitted bits carry zero LLRs — erasures), then **adds**
    ``prior`` — the combined channel LLRs of earlier HARQ rounds — so
    soft information accumulates across retransmissions.  ``rv`` may be a
    python int (static window) or an int array of leading batch shape
    (per-codeword RVs inside one compiled batch; the window becomes one
    gather).
    """
    pad = code.n_mother - code.e_bits
    buf = llr_e.astype(jnp.float32)
    if pad:
        zeros = jnp.zeros(llr_e.shape[:-1] + (pad,), jnp.float32)
        buf = jnp.concatenate([buf, zeros], axis=-1)
    if rv is not None and not (isinstance(rv, int) and rv % N_RV == 0):
        off = jnp.asarray(rv_offset(code, rv), jnp.int32)
        if off.ndim == 0:
            buf = jnp.roll(buf, off, axis=-1)
        else:
            # off has leading batch shape; mother bit i of codeword b was
            # received at window position (i - off[b]) mod n (zero pad
            # covers the untransmitted tail)
            n = code.n_mother
            off = off.reshape(off.shape + (1,) * (buf.ndim - off.ndim))
            idx = jnp.mod(jnp.arange(n, dtype=jnp.int32) - off, n)
            buf = jnp.take_along_axis(
                buf, jnp.broadcast_to(idx, buf.shape), axis=-1
            )
    if prior is not None:
        buf = buf + prior.astype(jnp.float32)
    return buf


# ---------------------------------------------------------------------------
# Mapping codewords onto the OFDM grid
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _data_re_index(grid: ofdm.GridConfig):
    """Static (sym_idx, sc_idx) arrays of the data REs in canonical
    (symbol-major, subcarrier-minor) order — the order codeword bits are
    laid onto the grid and gathered back."""
    union = ofdm.link_pilot_masks_np(grid).any(axis=0)
    sym, sc = np.nonzero(~union)
    # numpy, not jnp: a jnp array made while a trace is open would be
    # cached as that trace's tracer and leak into the next one
    return sym.astype(np.int32), sc.astype(np.int32)


def codewords_per_slot(scenario) -> int:
    """Whole codewords that fit a slot's data REs (rest is filler)."""
    code = scenario.code
    return scenario.data_bits_per_slot // code.e_bits


def info_bits_per_slot(scenario) -> int:
    """Payload (post-CRC) bits per slot — the goodput numerator."""
    return codewords_per_slot(scenario) * scenario.code.k_info


def goodput_bits(scenario, bler: float, n_slots: int) -> float:
    """Delivered payload bits for ``n_slots`` slots at block error ``bler``
    (error-free transport blocks only) — shared by the single-cell and
    mesh serve reports so the two always agree."""
    return (1.0 - bler) * info_bits_per_slot(scenario) * n_slots


def make_coded_slot(key: jax.Array, scenario, batch: int,
                    rv: Optional[int] = None,
                    info: Optional[jax.Array] = None) -> dict:
    """Simulate one coded uplink slot batch of ``scenario``.

    Draws per-slot transport blocks, CRC-attaches, LDPC-encodes and
    rate-matches them, lays the coded bits onto the grid's data REs in
    canonical order (trailing REs carry random filler), then runs the
    usual channel/noise simulation.  Adds ``info_bits`` (B, C, k_info)
    to the slot dict for BLER scoring.

    HARQ hooks: ``info`` re-transmits fixed transport blocks (a
    retransmission of the same codewords over a fresh channel/noise
    realization) and ``rv`` picks the redundancy-version window of the
    circular buffer; a non-None ``rv`` also stamps an ``rv`` array (B,)
    into the slot so the decode stage de-rate-matches per slot inside
    one compiled batch.

    Traceable: under ``jit`` ``rv`` and ``scenario.snr_db`` may be traced
    scalars (:class:`repro.serve.runtime.SlotGenerator` compiles this
    body once per scenario shape).
    """
    code, g = scenario.code, scenario.grid
    nb = scenario.modem.bits_per_symbol
    c = codewords_per_slot(scenario)
    assert c >= 1, (
        f"{scenario.name}: e_bits={code.e_bits} exceeds the slot's "
        f"{scenario.data_bits_per_slot} data bits"
    )
    kb_, kf, kc = jax.random.split(key, 3)
    if info is None:
        info = jax.random.bernoulli(
            kb_, 0.5, (batch, c, code.k_info)
        ).astype(jnp.int32)
    else:
        info = jnp.asarray(info, jnp.int32)
        assert info.shape == (batch, c, code.k_info), info.shape
    tx = rate_match(code, encode(code, crc_attach(info, code.crc_bits)),
                    rv=0 if rv is None else rv)
    flat = tx.reshape(batch, c * code.e_bits)
    n_fill = scenario.data_bits_per_slot - c * code.e_bits
    if n_fill:
        filler = jax.random.bernoulli(
            kf, 0.5, (batch, n_fill)
        ).astype(jnp.int32)
        flat = jnp.concatenate([flat, filler], axis=-1)

    sym_idx, sc_idx = _data_re_index(g)
    bits_data = flat.reshape(batch, len(sym_idx), g.n_tx, nb)
    bits = jnp.zeros(
        (batch, g.n_symbols, g.n_subcarriers, g.n_tx, nb), jnp.int32
    ).at[:, sym_idx, sc_idx].set(bits_data)

    slot = ofdm.make_link_slot(
        kc, g, scenario.modem, batch, scenario.snr_db,
        doppler_rho=scenario.doppler_rho, bits=bits,
        interferer_db=scenario.interferer_db,
        user_power_db=scenario.user_power_db,
    )
    slot["info_bits"] = info
    if rv is not None:
        slot["rv"] = jnp.full((batch,), rv, jnp.int32)
    return slot


def coded_llrs(scenario, llr: jax.Array) -> jax.Array:
    """Gather the per-codeword transmitted-bit LLRs back off the grid.

    llr (B, n_sym, n_sc, n_tx, nb) -> (B, C, e_bits), inverting the
    canonical layout of :func:`make_coded_slot` (filler REs dropped).
    """
    c = codewords_per_slot(scenario)
    e = scenario.code.e_bits
    sym_idx, sc_idx = _data_re_index(scenario.grid)
    data = llr[:, sym_idx, sc_idx]  # (B, n_data, n_tx, nb)
    return data.reshape(llr.shape[0], -1)[:, : c * e].reshape(
        llr.shape[0], c, e
    )


def decode_blocks(scenario, llr: jax.Array, *, max_iters: int = 12,
                  alpha: float = 0.8, use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None, rv=None,
                  prior_llr: Optional[jax.Array] = None,
                  precision: Optional[str] = None) -> dict:
    """Full receive-side coding chain on a finished detector state's LLRs.

    Returns ``info_bits_hat`` (B, C, k_info), ``crc_ok`` (B, C),
    ``decode_iters`` (B, C) and ``cw_llr`` (B, C, n_mother) — the decode
    stage in :mod:`repro.phy.link` merges these into the pipeline state.
    ``cw_llr`` is the *combined channel* LLR buffer (this transmission's
    de-rate-matched window plus ``prior_llr``): exactly what a HARQ
    entity must store to soft-combine the next retransmission, so the
    closed-loop runtime reads it straight off the state.
    """
    from repro.kernels import ldpc

    code = scenario.code
    cw_llr = derate_match(code, coded_llrs(scenario, llr), rv=rv,
                          prior=prior_llr)  # (B, C, n)
    b, c, n = cw_llr.shape
    post, iters = ldpc.ldpc_decode(
        cw_llr.reshape(b * c, n), code, max_iters=max_iters, alpha=alpha,
        use_pallas=use_pallas, interpret=interpret, precision=precision,
    )
    hard = (post[:, : code.k] > 0).astype(jnp.int32)
    ok = crc_check(hard, code.crc_bits)
    return {
        "info_bits_hat": hard[:, : code.k_info].reshape(b, c, code.k_info),
        "crc_ok": ok.reshape(b, c),
        "decode_iters": iters.reshape(b, c),
        "cw_llr": cw_llr,
    }
