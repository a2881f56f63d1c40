"""Fused classical-receiver kernels: parity vs the jnp oracles across every
registered scenario, Pallas(interpret) vs jnp-path agreement, full-pipeline
BER parity, and the block-shape autotuner cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, rx_fused, tune
from repro.phy import build_pipeline, ofdm
from repro.phy.scenarios import all_scenarios, get_scenario

KEY = jax.random.PRNGKey(7)

# scaled-down grids (same MIMO dims / modem as the registered scenarios) so
# the full sweep stays CI-sized; short channel keeps comb interp easy
_SMALL = dict(n_subcarriers=64, fft_size=64, n_taps=4, delay_spread=1.0)


def _small(name):
    scn = get_scenario(name)
    grid = dataclasses.replace(scn.grid, **_SMALL)
    return scn.replace(grid=grid)


def _detect_inputs(scn, batch=4):
    slot = scn.make_batch(KEY, batch)
    h = jnp.mean(slot["h"], axis=1)  # (B, n_sc, n_rx, n_tx)
    return slot, slot["y"], h, slot["noise_var"]


# ---------------------------------------------------------------------------
# fused equalize -> demap: parity across the whole scenario catalogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [s.name for s in all_scenarios()])
def test_detect_demap_parity_all_scenarios(name):
    """QPSK/16/64-QAM x SISO/2x2/4x8: the fused pass must agree with the
    unfused linalg-solve oracle — LLR signs >= 99.9%, soft outputs close."""
    scn = _small(name)
    _, y, h, nv = _detect_inputs(scn)
    xf, nvf, lf = rx_fused.mmse_detect_demap(
        y, h, nv, scn.modem, use_pallas=False
    )
    xr, nvr, lr = ref.mmse_detect_demap_ref(y, h, nv, scn.modem)
    np.testing.assert_allclose(
        np.asarray(xf), np.asarray(xr), rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(nvf), np.asarray(nvr), rtol=1e-3, atol=1e-4
    )
    sign_agree = float(jnp.mean((lf > 0) == (lr > 0)))
    assert sign_agree >= 0.999, (name, sign_agree)
    assert lf.shape == lr.shape == y.shape[:3] + (
        scn.grid.n_tx, scn.modem.bits_per_symbol
    )


@pytest.mark.parametrize("name",
                         ["mimo2x2-qam16-snr16", "mimo4x8-qam64-snr24",
                          "siso-qpsk-snr5"])
def test_detect_demap_pallas_matches_jnp_path(name):
    """The Pallas kernel body (interpret mode) computes the same fused math
    as the off-TPU jnp route."""
    scn = _small(name)
    _, y, h, nv = _detect_inputs(scn, batch=2)
    out_j = rx_fused.mmse_detect_demap_jnp(y, h, nv, scn.modem)
    out_p = rx_fused.mmse_detect_demap_pallas(
        y, h, nv, scn.modem, interpret=True
    )
    for a, b in zip(out_p, out_j):
        np.testing.assert_allclose(
            np.asarray(jnp.real(a)), np.asarray(jnp.real(b)),
            rtol=1e-3, atol=1e-3,
        )
    assert float(jnp.mean((out_p[2] > 0) == (out_j[2] > 0))) >= 0.999


def test_detect_demap_block_sc_tiling_invariance():
    """Subcarrier tiling must not change the result (64 = 2 tiles of 32)."""
    scn = _small("mimo2x2-qam16-snr16")
    _, y, h, nv = _detect_inputs(scn, batch=2)
    full = rx_fused.mmse_detect_demap_pallas(
        y, h, nv, scn.modem, block_sc=64, interpret=True
    )
    tiled = rx_fused.mmse_detect_demap_pallas(
        y, h, nv, scn.modem, block_sc=32, interpret=True
    )
    for a, b in zip(full, tiled):
        np.testing.assert_allclose(
            np.asarray(jnp.real(a)), np.asarray(jnp.real(b)),
            rtol=1e-5, atol=1e-5,
        )


# ---------------------------------------------------------------------------
# fused LS CHE
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [s.name for s in all_scenarios()])
def test_ls_che_parity_all_scenarios(name):
    scn = _small(name)
    cfg = scn.grid
    slot = scn.make_batch(KEY, 4)
    op = rx_fused.make_ls_interp_operator(
        cfg.n_subcarriers, cfg.n_tx, cfg.pilot_stride,
        np.asarray(ofdm.pilot_sequence(cfg)),
    )
    fused = rx_fused.ls_che(
        slot["y"], cfg.pilot_symbols, cfg.pilot_stride, op, use_pallas=False
    )
    oracle = ref.ls_che_ref(
        slot["y"], ofdm.pilot_sequence(cfg), ofdm.link_pilot_masks(cfg),
        cfg.pilot_stride,
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(oracle), rtol=1e-4, atol=1e-4
    )


def test_ls_che_pallas_matches_jnp_path():
    scn = _small("mimo2x2-qam16-snr16")
    cfg = scn.grid
    slot = scn.make_batch(KEY, 2)
    seq = np.asarray(ofdm.pilot_sequence(cfg))
    op = rx_fused.make_ls_interp_operator(
        cfg.n_subcarriers, cfg.n_tx, cfg.pilot_stride, seq,
    )
    a = rx_fused.ls_che_jnp(
        slot["y"], cfg.pilot_symbols, cfg.pilot_stride, op.dense(seq)
    )
    b = rx_fused.ls_che_pallas(
        slot["y"], cfg.pilot_symbols, cfg.pilot_stride, op,
        block_rows=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
    )


def test_ls_interp_operator_rejects_ragged_combs():
    with pytest.raises(AssertionError):
        rx_fused.make_ls_interp_operator(60, 2, 4, np.ones(60, np.complex64))


# ---------------------------------------------------------------------------
# full-pipeline BER parity (the mesh-engine gate: <= 2 borderline flips)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name", ["mimo2x2-qam16-snr16", "mimo4x8-qam16-snr12",
             "siso-qam64-snr24"]
)
def test_fused_pipeline_ber_parity(name):
    scn = _small(name)
    batch = scn.make_batch(KEY, 4)
    st_u = build_pipeline("classical", scn).run(batch)
    st_f = build_pipeline("classical", scn, fused=True).run(batch)
    hard_u, hard_f = st_u["llr"] > 0, st_f["llr"] > 0
    flips = jnp.sum(hard_u != hard_f, axis=tuple(range(1, hard_u.ndim)))
    assert int(jnp.max(flips)) <= 2, np.asarray(flips)
    # any flip must be a borderline LLR, not a real disagreement
    if int(jnp.sum(flips)):
        mag = jnp.where(hard_u != hard_f, jnp.abs(st_u["llr"]), 0.0)
        assert float(jnp.max(mag)) < 1e-2
    np.testing.assert_allclose(
        np.asarray(st_f["x_hat"]), np.asarray(st_u["x_hat"]),
        rtol=1e-3, atol=1e-3,
    )


def test_fused_pipeline_cycle_model_is_cheaper():
    """The fused chain's modeled TensorPool schedule must not be slower:
    fewer DMA round trips + the fused issue rate."""
    scn = get_scenario("mimo4x8-qam16-snr12")
    unfused = build_pipeline("classical", scn).total_cycles()
    fused = build_pipeline("classical", scn, fused=True).total_cycles()
    assert fused.concurrent() < unfused.concurrent()
    assert fused.dma_cycles < unfused.dma_cycles


def test_fused_flag_via_scenario_and_engine():
    """scenarios.build / PhyServeEngine.from_scenario expose the flag."""
    from repro.serve import PhyServeEngine

    scn = _small("mimo2x2-qam16-snr16")
    rx = scn.build("classical", fused=True)
    assert "fused" in rx.name and "detect_demap_fused" in rx.stage_cycles()
    eng = PhyServeEngine.from_scenario(scn, batch_size=2, fused=True)
    eng.submit_traffic(KEY, 2)
    rep = eng.run(warmup=False)
    assert rep.n_slots == 2 and rep.ber is not None


# ---------------------------------------------------------------------------
# classical.py satellites: cfft dispatch + shared Gram helper
# ---------------------------------------------------------------------------

def test_cfft_auto_handles_any_length():
    from repro.phy import classical

    x = jax.random.normal(KEY, (3, 12)) + 0j  # 12 is not a power of two
    np.testing.assert_allclose(
        np.asarray(classical.cfft_auto(x)), np.asarray(jnp.fft.fft(x)),
        rtol=1e-5, atol=1e-5,
    )
    # opt-in butterfly on radix-2 lengths matches the generic FFT...
    x2 = jax.random.normal(KEY, (3, 16)) + 0j
    np.testing.assert_allclose(
        np.asarray(classical.cfft_auto(x2, prefer_butterfly=True)),
        np.asarray(jnp.fft.fft(x2)), rtol=1e-4, atol=1e-4,
    )
    # ...and falls back to it (instead of asserting) off the radix-2 grid
    np.testing.assert_allclose(
        np.asarray(classical.cfft_auto(x, prefer_butterfly=True)),
        np.asarray(jnp.fft.fft(x)), rtol=1e-5, atol=1e-5,
    )


def test_pipeline_runs_on_non_radix2_grid():
    scn = get_scenario("mimo2x2-qam16-snr16").replace(
        grid=dataclasses.replace(
            get_scenario("mimo2x2-qam16-snr16").grid,
            n_subcarriers=48, fft_size=48, n_taps=4, delay_spread=1.0,
        )
    )
    for fused in (False, True):
        st = build_pipeline("classical", scn, fused=fused).run(
            scn.make_batch(KEY, 2)
        )
        assert bool(jnp.all(jnp.isfinite(st["llr"])))


def test_detectors_share_gram_assembly():
    """mimo_mmse_detect == biased ext output (one shared front end)."""
    from repro.phy import classical

    scn = _small("mimo4x8-qam16-snr12")
    slot = ofdm.make_mimo_slot(KEY, scn.grid, 4, 12.0)
    plain = classical.mimo_mmse_detect(
        slot["y"], slot["h"], slot["noise_var"]
    )
    x_u, _ = classical.mimo_mmse_detect_ext(
        slot["y"], slot["h"], slot["noise_var"]
    )
    gram, a, rhs = classical._regularized_gram_rhs(
        slot["y"], slot["h"], slot["noise_var"]
    )
    mu = jnp.clip(jnp.real(jnp.diagonal(
        jnp.linalg.solve(a, gram), axis1=-2, axis2=-1
    )), 1e-6, 1.0 - 1e-6)
    np.testing.assert_allclose(
        np.asarray(x_u * mu), np.asarray(plain), rtol=1e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# autotuner cache
# ---------------------------------------------------------------------------

def test_tune_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = tune.TuneCache(path)
    key = tune.cache_key("te_gemm", (256, 256, 384), "b2", backend="cpu")
    assert cache.lookup(key) is None
    cache.store(key, (128, 256, 128), us=42.0, n_candidates=9)
    # a fresh instance reads the persisted winner back
    assert tune.TuneCache(path).lookup(key) == (128, 256, 128)


def test_tune_cache_in_memory_without_path(tmp_path, monkeypatch):
    """With no path named, tuned winners live in the process only: nothing
    is read from or written to a default location."""
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    cache = tune.TuneCache()
    assert cache.path is None
    key = tune.cache_key("te_gemm", (256, 256, 384), "b2", backend="cpu")
    cache.store(key, (128, 256, 128), us=42.0)
    assert cache.lookup(key) == (128, 256, 128)
    assert tune.TuneCache().lookup(key) is None
    assert list(tmp_path.iterdir()) == []


def test_tune_cache_tolerates_corrupt_file(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text("{not json")
    assert tune.TuneCache(str(path)).lookup("anything") is None


def test_pick_block_shape_consults_cache(tmp_path, monkeypatch):
    from repro.kernels.te_gemm import pick_block_shape

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        heur = pick_block_shape(512, 512, 512, 2)
        tuned = (128, 128, 256)
        assert heur != tuned  # make the override observable
        tune.get_cache().store(
            tune.cache_key("te_gemm", (512, 512, 512), "b2"), tuned, 1.0
        )
        assert pick_block_shape(512, 512, 512, 2) == tuned
        # a stale cached shape that no longer divides is ignored
        heur_384 = pick_block_shape(384, 384, 384, 2)
        tune.get_cache().store(
            tune.cache_key("te_gemm", (384, 384, 384), "b2"),
            (256, 256, 256), 1.0,
        )
        assert pick_block_shape(384, 384, 384, 2) == heur_384
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune.set_cache_path(None)


def test_autotune_persists_winner_consumed_by_kernel(tmp_path, monkeypatch):
    """End-to-end: autotune -> JSON cache -> rx_fused picks the winner."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        scn = _small("mimo2x2-qam16-snr16")
        g = scn.grid
        choice = tune.autotune_rx_detect(
            1, g.n_symbols, g.n_subcarriers, g.n_rx, g.n_tx, scn.modem,
            iters=1,
        )
        assert g.n_subcarriers % choice[0] == 0
        key = tune.cache_key(
            "rx_detect_demap",
            (g.n_symbols, g.n_subcarriers, g.n_rx, g.n_tx,
             len(scn.modem.levels)),
        )
        assert tune.get_cache().lookup(key) == choice
        # the kernel resolves its tile through the cache without error
        _, y, h, nv = _detect_inputs(scn, batch=1)
        out = rx_fused.mmse_detect_demap_pallas(
            y, h, nv, scn.modem, interpret=True
        )
        assert bool(jnp.all(jnp.isfinite(out[2])))
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune.set_cache_path(None)


def test_ops_wrappers_jit_roundtrip():
    """The jitted ops wrappers accept the fused kernels' signatures."""
    scn = _small("mimo2x2-qam16-snr16")
    cfg = scn.grid
    slot, y, h, nv = _detect_inputs(scn, batch=2)
    x_hat, nv_eff, llr = ops.mmse_detect_demap(
        y, h, nv, scn.modem, use_pallas=False
    )
    assert llr.shape == y.shape[:3] + (cfg.n_tx, scn.modem.bits_per_symbol)
    op = rx_fused.make_ls_interp_operator(
        cfg.n_subcarriers, cfg.n_tx, cfg.pilot_stride,
        np.asarray(ofdm.pilot_sequence(cfg)),
    )
    h_ls = ops.ls_che(
        slot["y"], cfg.pilot_symbols, cfg.pilot_stride, op, use_pallas=False
    )
    assert h_ls.shape == (2, cfg.n_subcarriers, cfg.n_rx, cfg.n_tx)
