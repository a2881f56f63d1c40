"""Static PHY structure of a benchmark configuration, built from its file.

Everything the slot generator and the plain reference agree on lives
here: the resource grid and its DMRS combs, the gray QAM levels, the
base-graph-lite QC-LDPC protograph, the CRC-16 generator matrix, rate
matching and the canonical order in which codeword bits sit on the data
REs.  It follows the definitions the configuration file names and
imports nothing of the program under test.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

# gray-coded square QAM: per-axis amplitude of the axis-bit integer (MSB
# first) and the mean symbol energy of the unnormalised grid
MODEMS = {
    "qpsk": ((-1.0, 1.0), 2.0),
    "qam16": ((-3.0, -1.0, 3.0, 1.0), 10.0),
    "qam64": ((-7.0, -5.0, -1.0, -3.0, 7.0, 5.0, 1.0, 3.0), 42.0),
}
N_RV = 4  # redundancy versions around the circular buffer


@dataclasses.dataclass(frozen=True)
class Code:
    """One rate point of the base-graph-lite QC-LDPC family."""
    z: int
    k_b: int
    m_b: int
    p_tx_b: int
    info_edges: tuple  # per block row: ((block col, shift), ...)
    crc_bits: int
    crc_poly: int
    max_iters: int
    alpha: float

    @property
    def n_b(self) -> int:
        return self.k_b + self.m_b

    @property
    def k(self) -> int:
        return self.k_b * self.z

    @property
    def k_info(self) -> int:
        return self.k - self.crc_bits

    @property
    def n_mother(self) -> int:
        return self.n_b * self.z

    @property
    def e_bits(self) -> int:
        return (self.k_b + self.p_tx_b) * self.z

    def layers(self) -> tuple:
        """Block rows with the dual-diagonal parity circulants appended."""
        out = []
        for j in range(self.m_b):
            edges = list(self.info_edges[j])
            if j > 0:
                edges.append((self.k_b + j - 1, 0))
            edges.append((self.k_b + j, 0))
            out.append(tuple(edges))
        return tuple(out)

    def rv_offset(self, rv: int) -> int:
        return ((rv % N_RV) * self.n_b) // N_RV * self.z


def _protograph(k_b: int, m_b: int, z: int, col_degree: int,
                seed: int) -> tuple:
    """Each info block column lands in ``col_degree`` distinct block rows,
    least-loaded rows first with random tie-breaks, at a random shift."""
    rng = np.random.default_rng(seed)
    rows_of = [[] for _ in range(m_b)]
    for c in range(k_b):
        order = sorted(range(m_b),
                       key=lambda r: (len(rows_of[r]), rng.random()))
        for r in order[:col_degree]:
            rows_of[r].append((c, int(rng.integers(z))))
    return tuple(tuple(sorted(edges)) for edges in rows_of)


def make_code(code_cfg: dict, rate: str) -> Code:
    """``rate`` "r12" sends the whole rate-1/2 mother code; "r34" starts
    from the rate-2/3 mother and punctures its last two parity blocks."""
    k_b, z = code_cfg["k_b"], code_cfg["z"]
    m_b, p_tx = {"r12": (k_b, k_b), "r34": (k_b // 2, k_b // 3)}[rate]
    return Code(
        z=z, k_b=k_b, m_b=m_b, p_tx_b=p_tx,
        info_edges=_protograph(k_b, m_b, z, code_cfg["col_degree"],
                               code_cfg["protograph_seed"]),
        crc_bits=code_cfg["crc_bits"], crc_poly=int(code_cfg["crc_poly"], 16),
        max_iters=code_cfg["max_iters"], alpha=code_cfg["alpha"],
    )


@functools.lru_cache(maxsize=None)
def crc_matrix(k_info: int, poly: int, n_crc: int) -> np.ndarray:
    """(k_info, n_crc) with crc(bits) = bits @ M mod 2 (zero init, MSB
    first, no final xor): row i is the CRC of the unit message e_i."""
    m = np.zeros((k_info, n_crc), np.int32)
    for i in range(k_info):
        reg = 0
        for j in range(k_info):
            top = (reg >> (n_crc - 1)) & 1
            reg = (reg << 1) & ((1 << n_crc) - 1)
            if top ^ (1 if j == i else 0):
                reg ^= poly
        m[i] = [(reg >> (n_crc - 1 - b)) & 1 for b in range(n_crc)]
    return m


@dataclasses.dataclass(frozen=True)
class Rung:
    """One MCS rung of a configuration: grid, modem, code and channel."""
    name: str
    n_sc: int
    fft_size: int
    n_sym: int
    pilot_stride: int
    pilot_symbols: tuple
    n_tx: int
    n_rx: int
    n_taps: int
    delay_spread: float
    modulation: str
    snr_db: float
    user_power_db: tuple  # () when every stream is at 0 dB
    code: Code
    mmse_smooth: bool
    corr_len: float
    sic: bool

    @property
    def levels(self) -> tuple:
        return MODEMS[self.modulation][0]

    @property
    def norm(self) -> float:
        return MODEMS[self.modulation][1]

    @property
    def bits_per_symbol(self) -> int:
        return 2 * int(np.log2(len(self.levels)))

    def pilot_masks(self) -> np.ndarray:
        """(n_tx, n_sym, n_sc): tx t sounds subcarriers
        sc % (stride * n_tx) == t * stride of the DMRS symbols."""
        spacing = self.pilot_stride * self.n_tx
        sc = np.arange(self.n_sc)
        m = np.zeros((self.n_tx, self.n_sym, self.n_sc), bool)
        for t in range(self.n_tx):
            for s in self.pilot_symbols:
                m[t, s] = sc % spacing == t * self.pilot_stride
        return m

    def pilot_seq(self) -> np.ndarray:
        """(n_sc,) unit-power QPSK DMRS sequence."""
        k = np.arange(self.n_sc)
        return np.exp(1j * (np.pi / 4 + np.pi / 2 * (k % 4)))

    def data_re(self) -> tuple:
        """(sym, sc) of the data REs, symbol-major: the order codeword bits
        are laid onto the grid."""
        return np.nonzero(~self.pilot_masks().any(axis=0))

    @property
    def data_bits(self) -> int:
        return len(self.data_re()[0]) * self.n_tx * self.bits_per_symbol

    @property
    def codewords(self) -> int:
        return self.data_bits // self.code.e_bits

    @property
    def info_bits_per_slot(self) -> int:
        return self.codewords * self.code.k_info

    @property
    def noise_var(self) -> float:
        return self.n_tx / 10.0 ** (self.snr_db / 10.0)


def rungs(config: dict) -> list:
    """The configuration's rungs, in ladder order."""
    g, rx = config["grid"], config["receiver"]
    out = []
    for r in config["rungs"]:
        out.append(Rung(
            name=r["name"], n_sc=g["n_subcarriers"], fft_size=g["fft_size"],
            n_sym=g["n_symbols"], pilot_stride=g["pilot_stride"],
            pilot_symbols=tuple(g["pilot_symbols"]), n_tx=g["n_tx"],
            n_rx=g["n_rx"], n_taps=g["n_taps"],
            delay_spread=g["delay_spread"], modulation=r["modulation"],
            snr_db=r["snr_db"],
            user_power_db=tuple(config.get("user_power_db") or ()),
            code=make_code(config["code"], r["rate"]),
            mmse_smooth=rx["mmse_smooth"], corr_len=rx["corr_len"],
            sic=rx["sic"],
        ))
    return out


def rung(config: dict, name: str) -> Rung:
    for r in rungs(config):
        if r.name == name:
            return r
    raise KeyError(f"{config['name']} has no rung {name!r}")
