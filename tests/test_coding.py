"""Channel-coding subsystem: CRC, QC-LDPC encode/rate-matching, the layered
min-sum decoder (jnp vs Pallas-interpret vs numpy oracle), the coded
pipeline/serving path, and BLER behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ldpc, ref, tune
from repro.phy import build_pipeline, coding, ofdm, slot_metrics
from repro.phy.scenarios import get_scenario, scenario_names

KEY = jax.random.PRNGKey(11)

# small lifting so the per-row numpy oracle stays fast
CODE = coding.make_code("r12", z=16)
CODE34 = coding.make_code("r34", z=16)

_SMALL = dict(n_subcarriers=64, fft_size=64, n_taps=4, delay_spread=1.0)


def _small(name, **kw):
    scn = get_scenario(name)
    return scn.replace(grid=dataclasses.replace(scn.grid, **_SMALL), **kw)


def _noisy_llrs(code, batch, sigma, key=KEY, amp=2.0):
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (batch, code.k)).astype(jnp.int32)
    tx = coding.rate_match(code, coding.encode(code, bits))
    noise = jax.random.normal(kn, tx.shape) * sigma
    llr_e = (2.0 * tx - 1.0) * amp + amp * noise
    return bits, coding.derate_match(code, llr_e)


# ---------------------------------------------------------------------------
# CRC
# ---------------------------------------------------------------------------

def test_crc_roundtrip_and_detection():
    info = jax.random.bernoulli(KEY, 0.5, (8, 120)).astype(jnp.int32)
    word = coding.crc_attach(info)
    assert word.shape == (8, 120 + coding.CRC_BITS)
    assert bool(jnp.all(coding.crc_check(word)))
    # a forced single-bit error anywhere is caught
    for pos in (0, 57, 119, 120, 135):
        flipped = word.at[:, pos].set(1 - word[:, pos])
        assert not bool(jnp.any(coding.crc_check(flipped))), pos
    # burst errors are caught too (CRC-16 detects bursts <= 16)
    burst = word.at[:, 30:38].set(1 - word[:, 30:38])
    assert not bool(jnp.any(coding.crc_check(burst)))


def test_crc_matrix_matches_bitwise_division():
    """The GF(2)-matrix CRC equals a reference bitwise long division."""
    k = 40
    rng = np.random.default_rng(0)
    msg = rng.integers(0, 2, size=k)

    def crc_bitwise(bits):
        reg = 0
        for b in bits:
            top = (reg >> 15) & 1
            reg = (reg << 1) & 0xFFFF
            if top ^ int(b):
                reg ^= coding.CRC16_POLY
        return [(reg >> (15 - i)) & 1 for i in range(16)]

    got = np.asarray(coding.crc_attach(jnp.asarray(msg[None]))[0, k:])
    np.testing.assert_array_equal(got, crc_bitwise(msg))


# ---------------------------------------------------------------------------
# encode / rate matching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code", [CODE, CODE34], ids=["r12", "r34"])
def test_encode_satisfies_parity_checks(code):
    bits = jax.random.bernoulli(KEY, 0.5, (4, code.k)).astype(jnp.int32)
    cw = coding.encode(code, bits)
    h = coding.dense_parity_matrix(code)
    synd = (np.asarray(cw) @ h.T) % 2
    assert not synd.any()
    # systematic: the first k bits are the message
    np.testing.assert_array_equal(np.asarray(cw[:, : code.k]),
                                  np.asarray(bits))


def test_rate_match_roundtrip_and_puncturing():
    code = CODE34
    assert code.e_bits < code.n_mother  # r34 actually punctures
    cw = coding.encode(
        code,
        jax.random.bernoulli(KEY, 0.5, (2, code.k)).astype(jnp.int32),
    )
    tx = coding.rate_match(code, cw)
    assert tx.shape[-1] == code.e_bits
    llr = coding.derate_match(code, 2.0 * tx.astype(jnp.float32) - 1.0)
    assert llr.shape[-1] == code.n_mother
    # transmitted positions round-trip, punctured tail is erased (0 LLR)
    np.testing.assert_array_equal(
        np.asarray(llr[..., : code.e_bits] > 0), np.asarray(tx == 1)
    )
    assert not np.asarray(llr[..., code.e_bits:]).any()
    assert len(code.punctured_blocks()) * code.z == (
        code.n_mother - code.e_bits
    )


@pytest.mark.parametrize("code", [CODE, CODE34], ids=["r12", "r34"])
def test_rv_windows_scatter_to_circular_buffer_positions(code):
    """Every RV's transmitted window de-rate-matches back to its own
    circular-buffer positions; untransmitted bits stay erased."""
    cw = coding.encode(
        code,
        jax.random.bernoulli(KEY, 0.5, (3, code.k)).astype(jnp.int32),
    )
    cw_np = np.asarray(cw)
    for rv in range(coding.N_RV):
        tx = coding.rate_match(code, cw, rv=rv)
        llr = coding.derate_match(
            code, 2.0 * tx.astype(jnp.float32) - 1.0, rv=rv
        )
        off = int(coding.rv_offset(code, rv))
        pos = (off + np.arange(code.e_bits)) % code.n_mother
        mask = np.zeros(code.n_mother, bool)
        mask[pos] = True
        got = np.asarray(llr)
        np.testing.assert_array_equal(
            got[:, mask] > 0, cw_np[:, mask].astype(bool)
        )
        assert not got[:, ~mask].any()
        # per-codeword RV arrays (the compiled-batch path) agree with the
        # static-int path
        batched = coding.derate_match(
            code, (2.0 * tx.astype(jnp.float32) - 1.0)[:, None, :],
            rv=jnp.full((3,), rv, jnp.int32),
        )[:, 0]
        np.testing.assert_allclose(np.asarray(batched), got, atol=1e-6)


def test_derate_match_accumulates_prior_llrs():
    """HARQ soft combining: the prior buffer adds onto this round's
    window (chase on overlap, IR where the RV brings fresh bits)."""
    code = CODE34
    cw = coding.encode(
        code,
        jax.random.bernoulli(KEY, 0.5, (2, code.k)).astype(jnp.int32),
    )
    l0 = coding.derate_match(
        code, 2.0 * coding.rate_match(code, cw, rv=0).astype(jnp.float32) - 1.0
    )
    l1 = coding.derate_match(
        code,
        2.0 * coding.rate_match(code, cw, rv=1).astype(jnp.float32) - 1.0,
        rv=1, prior=l0,
    )
    l0n, l1n = np.asarray(l0), np.asarray(l1)
    # combined magnitudes never shrink (same codeword -> same signs)
    assert np.all(np.abs(l1n) >= np.abs(l0n) - 1e-6)
    # RV1 covered bits the RV0 window punctured: fewer erasures remain
    assert (l1n == 0).sum() < (l0n == 0).sum()
    # overlap region is chase-combined (doubled)
    assert np.isclose(np.abs(l1n).max(), 2.0)


def test_combined_decode_beats_single_shot():
    """Two noisy IR rounds decode where one round fails (fixed seed)."""
    code = CODE
    kb, k0, k1 = jax.random.split(KEY, 3)
    bits = jax.random.bernoulli(kb, 0.5, (8, code.k)).astype(jnp.int32)
    cw = coding.encode(code, bits)

    def rx_round(key, rv):
        tx = coding.rate_match(code, cw, rv=rv)
        noise = jax.random.normal(key, tx.shape)
        return (2.0 * tx - 1.0) * 0.9 + noise

    single = coding.derate_match(code, rx_round(k0, 0))
    combined = coding.derate_match(code, rx_round(k1, 1), rv=1,
                                   prior=single)

    def block_errors(llr):
        post, _ = ldpc.ldpc_decode(llr, code, use_pallas=False)
        hard = (post[:, : code.k] > 0).astype(jnp.int32)
        return int(jnp.sum(jnp.any(hard != bits, axis=-1)))

    e1, e2 = block_errors(single), block_errors(combined)
    assert e1 > 0, "test SNR too high to exercise combining"
    assert e2 < e1


def test_make_coded_slot_retransmission_carries_fixed_info_and_rv():
    scn = _small("siso-qam16-r34-snr18", snr_db=30.0)
    slot0 = scn.make_batch(KEY, 2)
    info = slot0["info_bits"]
    slot1 = coding.make_coded_slot(
        jax.random.PRNGKey(9), scn, 2, rv=2, info=info
    )
    np.testing.assert_array_equal(np.asarray(slot1["info_bits"]),
                                  np.asarray(info))
    np.testing.assert_array_equal(np.asarray(slot1["rv"]), [2, 2])
    assert "rv" not in slot0  # plain slots stay HARQ-free
    # the pipeline decodes the RV2 window at high SNR, and its cw_llr
    # output is the combined channel buffer (zeros where untransmitted)
    rx = build_pipeline("classical", scn)
    state = rx.run(slot1)
    assert float(slot_metrics(state, scn)["bler"]) == 0.0
    n_zero = int(np.sum(np.asarray(state["cw_llr"]) == 0.0))
    assert n_zero >= 2 * (scn.code.n_mother - scn.code.e_bits)


def test_code_rates_and_layers():
    assert abs(CODE.rate - 0.5) < 1e-9
    assert abs(CODE34.rate - 0.75) < 1e-9
    for code in (CODE, CODE34):
        layers = code.layers()
        assert len(layers) == code.m_b
        for edges in layers:
            cols = [c for c, _ in edges]
            assert len(cols) == len(set(cols))  # layer rows independent
            assert len(cols) >= 2  # min-sum needs degree >= 2


# ---------------------------------------------------------------------------
# decoder: round trip, parity across implementations, early exit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code", [CODE, CODE34], ids=["r12", "r34"])
def test_decode_roundtrip_high_snr(code):
    bits, llr = _noisy_llrs(code, 8, sigma=0.15)
    post, iters = ldpc.ldpc_decode(llr, code, use_pallas=False)
    hard = (post[:, : code.k] > 0).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(hard), np.asarray(bits))
    # clean channel: the syndrome already holds, decoding exits early
    assert int(jnp.max(iters)) <= 2


def test_decoder_corrects_errors_min_sum_actually_works():
    bits, llr = _noisy_llrs(CODE, 32, sigma=0.55, amp=1.0)
    raw = (llr[:, : CODE.k] > 0).astype(jnp.int32)
    assert int(jnp.sum(raw != bits)) > 0  # channel does flip bits
    post, iters = ldpc.ldpc_decode(llr, CODE, use_pallas=False)
    hard = (post[:, : CODE.k] > 0).astype(jnp.int32)
    dec_errs = int(jnp.sum(jnp.any(hard != bits, axis=-1)))
    raw_errs = int(jnp.sum(jnp.any(raw != bits, axis=-1)))
    assert dec_errs < raw_errs


def test_decode_jnp_matches_numpy_oracle():
    _, llr = _noisy_llrs(CODE, 6, sigma=0.6, amp=1.0)
    post_j, it_j = ldpc.ldpc_decode_jnp(llr, CODE)
    post_r, it_r = ref.ldpc_decode_ref(llr, CODE)
    np.testing.assert_allclose(
        np.asarray(post_j), np.asarray(post_r), rtol=1e-3, atol=1e-3
    )
    np.testing.assert_array_equal(np.asarray(it_j), np.asarray(it_r))


def test_decode_pallas_interpret_matches_jnp():
    _, llr = _noisy_llrs(CODE, 4, sigma=0.6, amp=1.0)
    post_j, it_j = ldpc.ldpc_decode_jnp(llr, CODE)
    post_p, it_p = ldpc.ldpc_decode_pallas(llr, CODE, interpret=True)
    np.testing.assert_allclose(
        np.asarray(post_p), np.asarray(post_j), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(it_p), np.asarray(it_j))


def test_decode_pallas_batch_tiling_invariance():
    _, llr = _noisy_llrs(CODE, 8, sigma=0.5, amp=1.0)
    full = ldpc.ldpc_decode_pallas(llr, CODE, block_b=8, interpret=True)
    tiled = ldpc.ldpc_decode_pallas(llr, CODE, block_b=2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(full[0]), np.asarray(tiled[0]), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(full[1]), np.asarray(tiled[1]))


def test_early_exit_iteration_counts():
    max_iters = 12
    # clean input: zero iterations, posterior untouched
    bits, clean = _noisy_llrs(CODE, 4, sigma=0.0)
    post, iters = ldpc.ldpc_decode(clean, CODE, use_pallas=False,
                                   max_iters=max_iters)
    assert int(jnp.max(iters)) == 0
    np.testing.assert_allclose(np.asarray(post), np.asarray(clean))
    # noisy input: effort rises but never exceeds the cap
    _, noisy = _noisy_llrs(CODE, 16, sigma=0.7, amp=1.0)
    _, iters_n = ldpc.ldpc_decode(noisy, CODE, use_pallas=False,
                                  max_iters=max_iters)
    assert int(jnp.max(iters_n)) <= max_iters
    assert float(jnp.mean(iters_n)) > 0.5


def test_autotune_ldpc_persists_winner(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        choice = tune.autotune_ldpc(8, CODE, max_iters=4, iters=1)
        assert choice[0] % ldpc.LANE == 0
        key = tune.cache_key(
            "ldpc_decode", (CODE.k_b, CODE.m_b, CODE.z, 4)
        )
        assert tune.get_cache().lookup(key) == choice
        # the kernel resolves its batch tile through the cache
        _, llr = _noisy_llrs(CODE, 8, sigma=0.4)
        out = ldpc.ldpc_decode_pallas(llr, CODE, max_iters=4,
                                      interpret=True)
        assert bool(jnp.all(jnp.isfinite(out[0])))
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune.set_cache_path(None)


# ---------------------------------------------------------------------------
# coded slots: grid mapping, pipeline, metrics
# ---------------------------------------------------------------------------

def test_coded_slot_grid_mapping_roundtrip():
    """Bits laid onto the grid gather back as the transmitted codewords."""
    scn = _small("siso-qpsk-r12-snr8")
    slot = scn.make_batch(KEY, 2)
    assert slot["info_bits"].shape == (
        2, coding.codewords_per_slot(scn), scn.code.k_info
    )
    # pretend-perfect LLRs straight from the transmitted bits
    fake_llr = 2.0 * slot["bits"].astype(jnp.float32) - 1.0
    gathered = coding.coded_llrs(scn, fake_llr) > 0
    expect = coding.rate_match(
        scn.code,
        coding.encode(
            scn.code, coding.crc_attach(slot["info_bits"],
                                        scn.code.crc_bits)
        ),
    )
    np.testing.assert_array_equal(np.asarray(gathered),
                                  np.asarray(expect == 1))


def test_data_re_index_first_built_inside_a_trace():
    """The data-RE index is cached per grid; built first inside ``jit``
    it must not keep that trace's tracer for the next trace or an eager
    call on the same grid."""
    scn = _small("siso-qpsk-r12-snr8")
    # a grid no other test uses, so the first index build is in the trace
    scn = scn.replace(grid=dataclasses.replace(scn.grid, n_subcarriers=60))
    llr = jnp.ones((1, scn.grid.n_symbols, scn.grid.n_subcarriers, 1, 2))
    jax.jit(lambda x: coding.coded_llrs(scn, x))(llr)
    jax.jit(lambda x: coding.coded_llrs(scn, x * 2.0))(llr)
    jax.jit(lambda k: coding.make_coded_slot(k, scn, 1)["bits"])(KEY)
    slot = coding.make_coded_slot(KEY, scn, 1)
    np.testing.assert_array_equal(
        np.asarray(coding.coded_llrs(scn, slot["bits"])),
        np.asarray(coding.rate_match(scn.code, coding.encode(
            scn.code, coding.crc_attach(slot["info_bits"])))),
    )


def test_coded_scenarios_registered_and_build_everywhere():
    coded = [n for n in scenario_names() if get_scenario(n).coded]
    assert len(coded) >= 4
    rates = {get_scenario(n).code.name for n in coded}
    assert len(rates) >= 2  # at least two rate points
    assert any(get_scenario(n).is_mimo for n in coded)
    # the scenario contract: every receiver builds out of the box
    scn = _small("siso-qam16-r12-snr15")
    for kind in ("classical", "deeprx", "cevit"):
        rx = build_pipeline(kind, scn)
        assert rx.stages[-1].name == "ldpc_decode"


def test_coded_pipeline_end_to_end_metrics():
    scn = _small("siso-qpsk-r12-snr8", snr_db=20.0)
    rx = build_pipeline("classical", scn)
    state = rx.run(scn.make_batch(KEY, 4))
    assert set(state) >= {"info_bits_hat", "crc_ok", "decode_iters"}
    m = slot_metrics(state, scn)
    assert 0.0 <= float(m["bler"]) <= 1.0
    assert float(m["decode_iters"]) >= 0.0
    # at 20 dB the rate-1/2 link is essentially error-free
    assert float(m["bler"]) <= 0.25
    assert bool(jnp.mean(state["crc_ok"].astype(jnp.float32)) >= 0.75)
    # per-slot metrics keep the batch axis
    per = slot_metrics(state, scn, per_slot=True)
    assert per["bler"].shape == (4,)


def test_coded_pipeline_fused_variant_parity():
    scn = _small("siso-qam16-r12-snr15", snr_db=22.0)
    batch = scn.make_batch(KEY, 2)
    st_u = build_pipeline("classical", scn).run(batch)
    st_f = build_pipeline("classical", scn, fused=True).run(batch)
    # decoded transport blocks agree (decoder sits behind either demap)
    agree = float(jnp.mean(
        (st_u["info_bits_hat"] == st_f["info_bits_hat"]).astype(jnp.float32)
    ))
    assert agree >= 0.99


def test_bler_monotone_in_snr():
    base = _small("siso-qpsk-r12-snr8")
    blers = []
    for snr in (2.0, 10.0, 24.0):
        scn = base.replace(snr_db=snr)
        rx = build_pipeline("classical", scn)
        m = slot_metrics(rx.run(scn.make_batch(jax.random.PRNGKey(3), 8)),
                         scn)
        blers.append(float(m["bler"]))
    # non-increasing up to Monte-Carlo noise on the small test grid
    assert blers[1] <= blers[0] + 0.05
    assert blers[2] <= blers[1] + 0.05
    assert blers[2] <= 0.2  # high SNR end of the waterfall is clean


def test_decode_stage_cycle_model():
    scn = get_scenario("siso-qam16-r12-snr15")
    rx = build_pipeline("classical", scn)
    cyc = rx.stage_cycles()["ldpc_decode"]
    assert cyc.pe_cycles > 0 and cyc.dma_cycles > 0 and cyc.te_cycles > 0
    # the coded chain still fits the paper's 1 ms TTI at batch 4
    assert rx.tti_report(batch=4)["fits_tti"]


# ---------------------------------------------------------------------------
# serving: single cell + mesh
# ---------------------------------------------------------------------------

def test_phy_serve_reports_bler_and_goodput():
    from repro.serve import PhyServeEngine

    scn = _small("siso-qpsk-r12-snr8", snr_db=16.0)
    eng = PhyServeEngine.from_scenario(scn, batch_size=2)
    eng.submit_traffic(KEY, 4)
    rep = eng.run(warmup=False)
    assert rep.bler is not None and 0.0 <= rep.bler <= 1.0
    assert rep.info_bits_per_sec is not None and rep.info_bits_per_sec >= 0
    assert rep.decode_iters is not None
    assert "BLER=" in rep.summary() and "goodput=" in rep.summary()
    # uncoded scenarios keep reporting None
    unc = _small("siso-qam16-snr12")
    eng2 = PhyServeEngine.from_scenario(unc, batch_size=2)
    eng2.submit_traffic(KEY, 2)
    rep2 = eng2.run(warmup=False)
    assert rep2.bler is None and rep2.info_bits_per_sec is None


def test_cell_mesh_coded_cells_group_and_report():
    from repro.serve import CellMeshEngine, cell

    coded = _small("siso-qpsk-r12-snr8", snr_db=14.0)
    uncoded = _small("siso-qpsk-snr5", snr_db=14.0)
    eng = CellMeshEngine(
        [cell("c0", coded), cell("c1", coded), cell("u0", uncoded)],
        batch_size=2,
    )
    # same grid+modulation, but the code splits the shape group
    assert len(eng.groups) == 2
    eng.submit_traffic(KEY, 2)
    rep = eng.run(warmup=False)
    assert rep.bler is not None
    assert rep.info_bits_per_sec is not None
    assert rep.cells["c0"].bler is not None
    assert rep.cells["u0"].bler is None
    assert "BLER=" in rep.summary()
