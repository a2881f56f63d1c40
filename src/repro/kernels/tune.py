"""Block-shape autotuner for the Pallas kernels.

The static ``pick_block_shape`` heuristic solves Kung's inequality from the
machine constants — good on paper, but the best tiling on real hardware
depends on compiler scheduling that no closed form captures.  This module
measures: it times candidate tilings per (op, shape, dtype, backend) and
persists the winner to a JSON cache that ``te_gemm`` / ``mha`` /
``rx_fused`` consult before falling back to the heuristic.

Cache entries are keyed by backend (``cpu`` / ``tpu`` / ``gpu``), so a
cache tuned in interpret mode never leaks onto hardware and vice versa.

Cache file format (JSON)::

    {
      "version": 1,
      "entries": {
        "te_gemm|512x512x512|b2|cpu": {
          "choice": [256, 256, 128],
          "us": 1234.5,
          "n_candidates": 9
        }
      }
    }

The cache persists only to a file named by the ``REPRO_TUNE_CACHE``
environment variable or :func:`set_cache_path` (tests use a tmp path).
With neither, it lives in memory for the process, so kernels run on their
static default tilings plus whatever this process tuned — never on a file
left somewhere outside the checkout.  Lookups are tolerant: a
missing/corrupt cache or a stale entry that no longer divides the problem
shape is ignored.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional, Sequence

import jax

_ENV_VAR = "REPRO_TUNE_CACHE"
_ORIG_ENV = os.environ.get(_ENV_VAR)  # restored by set_cache_path(None)
_VERSION = 1


def default_cache_path() -> Optional[str]:
    """``$REPRO_TUNE_CACHE``, or None (an in-memory cache)."""
    return os.environ.get(_ENV_VAR)


def cache_key(op: str, shape: Sequence[int], extra: str = "",
              backend: Optional[str] = None,
              objective: str = "latency") -> str:
    backend = backend or jax.default_backend()
    dims = "x".join(str(int(d)) for d in shape)
    obj = "" if objective == "latency" else f"obj-{objective}"
    return "|".join(p for p in (op, dims, extra, obj, backend) if p)


class TuneCache:
    """Persistent (op, shape, dtype, backend) -> block-shape winners."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._entries: Optional[dict] = None  # lazy

    # -- persistence ------------------------------------------------------
    def _load(self) -> dict:
        if self._entries is None:
            self._entries = {}
            if self.path is None:
                return self._entries
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if isinstance(data, dict) and data.get("version") == _VERSION:
                    self._entries = dict(data.get("entries", {}))
            except (OSError, ValueError):
                pass  # missing/corrupt cache == empty cache
        return self._entries

    def save(self):
        """Atomically persist the cache: write a sibling tmp file and
        ``os.replace`` it over the target, so an interrupted or
        concurrent run can never leave a truncated cache behind (a
        corrupt file would otherwise poison block-shape selection until
        manually deleted — ``_load`` regenerates from empty instead).
        An in-memory cache (no path) has nothing to persist."""
        if self.path is None:
            return
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        payload = {"version": _VERSION, "entries": self._load()}
        tmp = os.path.join(d, f".{os.path.basename(self.path)}.{os.getpid()}.tmp")
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)

    # -- access -----------------------------------------------------------
    def lookup(self, key: str) -> Optional[tuple]:
        ent = self._load().get(key)
        if not ent or "choice" not in ent:
            return None
        return tuple(ent["choice"])

    def store(self, key: str, choice: Sequence[int], us: float,
              n_candidates: int = 0, save: bool = True):
        self._load()[key] = {
            "choice": [int(c) for c in choice],
            "us": round(float(us), 1),
            "n_candidates": int(n_candidates),
        }
        if save:
            self.save()

    def clear(self):
        self._entries = {}


_CACHE: Optional[TuneCache] = None


def get_cache() -> TuneCache:
    global _CACHE
    if _CACHE is None or _CACHE.path != default_cache_path():
        _CACHE = TuneCache()
    return _CACHE


def set_cache_path(path: Optional[str]):
    """Point the process-wide cache at ``path``.

    ``None`` restores the environment as it was at import time (an
    operator-set ``REPRO_TUNE_CACHE`` survives a set/reset cycle).
    """
    global _CACHE
    if path is None:
        if _ORIG_ENV is None:
            os.environ.pop(_ENV_VAR, None)
        else:
            os.environ[_ENV_VAR] = _ORIG_ENV
    else:
        os.environ[_ENV_VAR] = path
    _CACHE = None


def cached_choice(op: str, shape: Sequence[int], extra: str = "",
                  objective: str = "latency") -> Optional[tuple]:
    """The persisted winner for (op, shape, extra) on this backend, if any."""
    return get_cache().lookup(cache_key(op, shape, extra,
                                        objective=objective))


# ---------------------------------------------------------------------------
# timing + generic search
# ---------------------------------------------------------------------------

def _median_us(fn: Callable, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def autotune(op: str, shape: Sequence[int], candidates: Sequence[tuple],
             run: Callable[[tuple], object], *, extra: str = "",
             iters: int = 3, cache: Optional[TuneCache] = None,
             objective: str = "latency",
             energy_fn: Optional[Callable[[tuple, float], float]] = None,
             ) -> tuple:
    """Measure ``run(candidate)`` for every candidate, persist + return the
    winner.  ``run`` must return a jax value (blocked on for timing).

    ``objective="latency"`` picks the minimum median microseconds.
    ``objective="energy"`` picks the minimum *modeled joules per call*:
    ``energy_fn(candidate, us)`` prices the candidate's dynamic energy
    (its tiling decides the HBM<->VMEM stream traffic) plus the static
    power burned over the measured wall time — so a tiling that trades a
    little latency for a lot less traffic can win.  The two objectives
    persist under distinct cache keys (round-trippable side by side).
    """
    assert candidates, f"no tiling candidates for {op} {shape}"
    assert objective in ("latency", "energy"), objective
    if objective == "energy":
        assert energy_fn is not None, "objective='energy' needs energy_fn"
    cache = cache or get_cache()
    best = None
    for cand in candidates:
        us = _median_us(lambda: run(cand), iters=iters)
        score = us if objective == "latency" else energy_fn(cand, us)
        if best is None or score < best[0]:
            best = (score, us, cand)
    _, us, choice = best
    cache.store(cache_key(op, shape, extra, objective=objective), choice,
                us, n_candidates=len(candidates))
    return choice


# ---------------------------------------------------------------------------
# per-op tuners (lazy kernel imports keep this module dependency-free)
# ---------------------------------------------------------------------------

def _divisor_cands(n: int, cands: Sequence[int]) -> list[int]:
    out = [c for c in cands if c <= n and n % c == 0]
    return out or [n]


def gemm_energy_fn(m: int, n: int, k: int, precision: str,
                   out_bytes: int = 4) -> Callable[[tuple, float], float]:
    """Modeled joules/call for a te_gemm tiling: MAC energy at the dtype's
    pJ/MAC (tiling-invariant) + HBM<->VMEM stream traffic priced at the DMA
    pJ/byte (X re-streams n/bn times, W m/bm times, Z written once) +
    static power over the measured wall time."""
    from repro.analysis import costmodel as _cm
    from repro.kernels import quant as _q

    nbytes = _q.itemsize(precision)
    pj_mac = _cm.PJ_PER_MAC[_q.resolve_precision(precision)]

    def joules(cand: tuple, us: float) -> float:
        bm, bn, bk = cand
        bytes_moved = (nbytes * (m * k * (n // bn) + k * n * (m // bm))
                       + out_bytes * m * n)
        dyn_pj = m * n * k * pj_mac + bytes_moved * _cm.PJ_PER_BYTE_DMA
        return dyn_pj * 1e-12 + _cm.STATIC_W * us * 1e-6

    return joules


def autotune_gemm(m: int, n: int, k: int, dtype=None, *,
                  iters: int = 3, cache: Optional[TuneCache] = None,
                  objective: str = "latency") -> tuple:
    """Tune (bm, bn, bk) for ``te_gemm`` at (m, n, k) and persist it.

    Keys on the dtype *name* (``bfloat16`` / ``int8`` / ``float8_e4m3fn``),
    never on itemsize — the 1-byte dtypes would collide.  Quantized dtypes
    run the quantized kernel so the winner reflects the dequant epilogue.
    """
    import jax.numpy as jnp

    from repro.core.balance import tile_vmem_bytes
    from repro.core.machine import TPU_V5E
    from repro.kernels import quant as _q
    from repro.kernels import te_gemm as _te

    dtype = dtype or jnp.bfloat16
    dtype = jnp.dtype(dtype)
    precision = _q.precision_of_dtype(dtype)
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    if _q.is_quantized(precision):
        run = lambda c: _te.te_gemm_quant(
            x, w, precision=precision, block_shape=c
        )
    else:
        x, w = x.astype(dtype), w.astype(dtype)
        run = lambda c: _te.te_gemm(x, w, block_shape=c)
    budget = TPU_V5E.fast_mem_bytes // 2
    cands = [
        (bm, bn, bk)
        for bm in _divisor_cands(m, (512, 256, 128))
        for bn in _divisor_cands(n, (512, 256, 128))
        for bk in _divisor_cands(k, (512, 256, 128))
        if tile_vmem_bytes(bm, bn, bk, dtype.itemsize) <= budget
    ]
    return autotune(
        "te_gemm", (m, n, k), cands, run,
        extra=_q.dtype_name(dtype), iters=iters, cache=cache,
        objective=objective,
        energy_fn=gemm_energy_fn(m, n, k, precision),
    )


def autotune_mha(bh: int, sq: int, sk: int, d: int, *, causal: bool = True,
                 iters: int = 3, cache: Optional[TuneCache] = None) -> tuple:
    """Tune (bq, bkv) for the flash-MHA kernel and persist it."""
    import jax.numpy as jnp

    from repro.kernels import mha as _mha

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.float32)
               for kk, s in zip(ks, (sq, sk, sk)))
    cands = [
        (bq, bkv)
        for bq in _divisor_cands(sq, (256, 128))
        for bkv in _divisor_cands(sk, (256, 128))
    ]
    return autotune(
        "mha", (bh, sq, sk, d), cands,
        lambda c: _mha.mha(q, k, v, causal=causal, bq=c[0], bkv=c[1]),
        iters=iters, cache=cache,
    )


def autotune_rx_detect(batch: int, n_sym: int, n_sc: int, n_rx: int,
                       n_tx: int, modem, *, iters: int = 3,
                       cache: Optional[TuneCache] = None) -> tuple:
    """Tune the subcarrier tile (bs,) of the fused detect+demap kernel."""
    import jax.numpy as jnp

    from repro.kernels import rx_fused as _rx

    kk = jax.random.split(jax.random.PRNGKey(0), 4)
    cplx = lambda k, shp: (jax.random.normal(k[0], shp)
                           + 1j * jax.random.normal(k[1], shp))
    y = cplx(kk[:2], (batch, n_sym, n_sc, n_rx))
    h = cplx(kk[2:], (batch, n_sc, n_rx, n_tx))
    nv = jnp.asarray(0.1, jnp.float32)
    cands = [(bs,) for bs in _divisor_cands(n_sc, (512, 256, 128, 64))]
    return autotune(
        "rx_detect_demap", (n_sym, n_sc, n_rx, n_tx, len(modem.levels)),
        cands,
        lambda c: _rx.mmse_detect_demap_pallas(
            y, h, nv, modem, block_sc=c[0]
        )[2],
        iters=iters, cache=cache,
    )


def autotune_rx_sic(batch: int, n_sym: int, n_sc: int, n_rx: int,
                    n_tx: int, modem, *, iters: int = 3,
                    cache: Optional[TuneCache] = None) -> tuple:
    """Tune the subcarrier tile (bs,) of the fused SIC detect+demap kernel.

    Tuned separately from ``rx_detect_demap``: the SIC core runs ~n_tx
    shrinking Gram/Gauss solves per tile, so its best tile is usually
    smaller than the joint-LMMSE kernel's.
    """
    import jax.numpy as jnp

    from repro.kernels import rx_fused as _rx

    kk = jax.random.split(jax.random.PRNGKey(0), 4)
    cplx = lambda k, shp: (jax.random.normal(k[0], shp)
                           + 1j * jax.random.normal(k[1], shp))
    y = cplx(kk[:2], (batch, n_sym, n_sc, n_rx))
    h = cplx(kk[2:], (batch, n_sc, n_rx, n_tx))
    nv = jnp.asarray(0.1, jnp.float32)
    cands = [(bs,) for bs in _divisor_cands(n_sc, (512, 256, 128, 64))]
    return autotune(
        "rx_sic_demap", (n_sym, n_sc, n_rx, n_tx, len(modem.levels)),
        cands,
        lambda c: _rx.sic_detect_demap_pallas(
            y, h, nv, modem, block_sc=c[0]
        )[2],
        iters=iters, cache=cache,
    )


def autotune_ldpc(batch: int, code, *, max_iters: int = 12,
                  iters: int = 3, cache: Optional[TuneCache] = None) -> tuple:
    """Tune the batch tile (bt,) of the layered LDPC decoder kernel."""
    import jax.numpy as jnp

    from repro.kernels import ldpc as _ldpc
    from repro.phy import coding as _coding

    kb, kn = jax.random.split(jax.random.PRNGKey(0))
    bits = jax.random.bernoulli(
        kb, 0.5, (batch, code.k)
    ).astype(jnp.int32)
    cw = _coding.encode(code, bits)
    noise = jax.random.normal(kn, cw.shape) * 0.7
    llr = _coding.derate_match(
        code, ((2.0 * cw - 1.0) * 3.0 + noise)[..., : code.e_bits]
    )
    # lane tiles are whole multiples of 128 codewords (Mosaic's lane
    # quantum); the kernel pads the batch up to a whole tile
    padded = -(-batch // _ldpc.LANE) * _ldpc.LANE
    cands = [(bt,) for bt in _divisor_cands(padded, (512, 256, 128))]
    return autotune(
        "ldpc_decode", (code.k_b, code.m_b, code.z, max_iters), cands,
        lambda c: _ldpc.ldpc_decode_pallas(
            llr, code, max_iters=max_iters, block_b=c[0]
        )[0],
        iters=iters, cache=cache,
    )


def autotune_rx_ls_che(batch: int, n_sym: int, n_sc: int, n_rx: int,
                       n_tx: int, pilot_stride: int,
                       pilot_symbols: tuple = (2, 11), *, iters: int = 3,
                       cache: Optional[TuneCache] = None) -> tuple:
    """Tune the row tile (bm,) of the fused LS-CHE interp-GEMM kernel."""
    import numpy as np

    from repro.kernels import rx_fused as _rx

    kr, ki = jax.random.split(jax.random.PRNGKey(0))
    shp = (batch, n_sym, n_sc, n_rx)
    y = jax.random.normal(kr, shp) + 1j * jax.random.normal(ki, shp)
    seq = np.exp(1j * (np.pi / 4 + np.pi / 2 * (np.arange(n_sc) % 4)))
    op = _rx.make_ls_interp_operator(n_sc, n_tx, pilot_stride, seq)
    rows = batch * n_rx
    cands = [(bm,) for bm in _divisor_cands(rows, (64, 32, 16, 8))]
    return autotune(
        "rx_ls_che", (n_sc, n_rx, n_tx, op.n_p), cands,
        lambda c: _rx.ls_che_pallas(
            y, pilot_symbols, pilot_stride, op, block_rows=c[0]
        ),
        iters=iters, cache=cache,
    )
