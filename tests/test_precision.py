"""Quantized (int8/fp8) kernel paths: parity vs the fp32 oracles, the
LLR grid, and the dtype-aware / energy-aware tune cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import mha, quant, ref, rx_fused, te_gemm, tune
from repro.phy import coding
from repro.phy.scenarios import get_scenario

KEY = jax.random.PRNGKey(0)


def _link_llrs(scn, batch, key=KEY):
    """Run the fp32 classical chain up to the decoder; returns (llr, slot)."""
    pipe = scn.build(receiver="classical")
    state = dict(scn.make_batch(key, batch))
    for st in pipe.stages:
        if st.name == "ldpc_decode":
            break
        state = st.apply(state)
    return state["llr"], state


def _bler(out, state):
    blk = jnp.any(out["info_bits_hat"] != state["info_bits"], axis=-1)
    return float(jnp.mean(blk.astype(jnp.float32)))


# -- precision policy -------------------------------------------------------

def test_resolve_precision_aliases():
    assert quant.resolve_precision(None) == "fp32"
    assert quant.resolve_precision("float16") == "fp16"
    assert quant.resolve_precision("e4m3") == "fp8"
    assert quant.is_quantized("int8") and quant.is_quantized("fp8")
    assert not quant.is_quantized("bf16")
    with pytest.raises(ValueError):
        quant.resolve_precision("int4")


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(KEY, (64, 64), jnp.float32)
    for p, tol in (("int8", 0.02), ("fp8", 0.08)):
        q, s = quant.quantize(x, p, axis=1)
        back = quant.dequantize(q, s)
        rel = float(jnp.linalg.norm(back - x) / jnp.linalg.norm(x))
        assert rel < tol, (p, rel)


def test_itemsize_counts_quantized_as_one_byte():
    assert quant.itemsize("int8") == 1
    assert quant.itemsize("fp8") == 1
    assert quant.itemsize("fp16") == 2
    assert quant.itemsize("fp32") == 4


# -- quantized GEMM ---------------------------------------------------------

@pytest.mark.parametrize("precision,tol", [("int8", 0.03), ("fp8", 0.08)])
def test_te_gemm_quant_matches_oracle(precision, tol):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (128, 128), jnp.float32)
    w = jax.random.normal(k2, (128, 128), jnp.float32)
    want = ref.te_gemm_ref(x, w, None, "none")
    got = te_gemm.te_gemm_quant_jnp(x, w, precision=precision)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < tol, rel


@pytest.mark.parametrize("epilogue", ["none", "bias_relu"])
def test_te_gemm_quant_pallas_matches_jnp(epilogue):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (128, 128), jnp.float32)
    w = jax.random.normal(k2, (128, 128), jnp.float32)
    bias = (jax.random.normal(k3, (128,), jnp.float32)
            if epilogue != "none" else None)
    want = te_gemm.te_gemm_quant_jnp(
        x, w, bias, precision="int8", epilogue=epilogue
    )
    got = te_gemm.te_gemm_quant(
        x, w, bias, precision="int8", epilogue=epilogue,
        block_shape=(64, 64, 64), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- quantized MHA ----------------------------------------------------------

@pytest.mark.parametrize("precision,tol", [("int8", 0.05), ("fp8", 0.2)])
def test_mha_quant_matches_oracle(precision, tol):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 128, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 128, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 128, 64), jnp.float32)
    want = ref.mha_ref(q, k, v, causal=True)
    got = mha.mha_quant_jnp(q, k, v, precision=precision, causal=True)
    assert float(jnp.max(jnp.abs(got - want))) < tol


def test_mha_quant_pallas_matches_jnp():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 128, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 128, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 128, 64), jnp.float32)
    want = mha.mha_quant_jnp(q, k, v, precision="int8", causal=True)
    got = mha.mha_quant(q, k, v, precision="int8", causal=True,
                        bq=64, bkv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- quantized LLR plane ----------------------------------------------------

def test_demap_quantized_grid_and_sign_agreement():
    scn = get_scenario("siso-qam16-r12-snr15")
    slot = scn.make_batch(KEY, 4)
    y, nv = slot["y"], slot["noise_var"]
    h = jnp.mean(slot["h"], axis=1)
    llr = rx_fused.mmse_detect_demap(y, h, nv, scn.modem)[2]
    llr_q = rx_fused.mmse_detect_demap(
        y, h, nv, scn.modem, precision="int8"
    )[2]
    agree = float(jnp.mean((llr_q > 0) == (llr > 0)))
    assert agree >= 0.99, agree
    # every quantized LLR lands on the int8 grid
    step = quant.llr_scale()
    codes = np.asarray(llr_q) / step
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-4)
    assert np.max(np.abs(codes)) <= 127.0 + 1e-6


def test_demap_int8_returns_codes_and_scale():
    scn = get_scenario("siso-qam16-r12-snr15")
    slot = scn.make_batch(KEY, 2)
    y, nv = slot["y"], slot["noise_var"]
    h = jnp.mean(slot["h"], axis=1)
    x_hat, nv_eff, q, s = rx_fused.mmse_detect_demap_int8(
        y, h, nv, scn.modem
    )
    assert q.dtype == jnp.int8
    want = rx_fused.mmse_detect_demap(
        y, h, nv, scn.modem, precision="int8"
    )[2]
    np.testing.assert_allclose(
        np.asarray(quant.dequantize_llr(q, s)), np.asarray(want),
        rtol=1e-5, atol=1e-5,
    )


# -- int8 layered min-sum ---------------------------------------------------

def test_int8_ldpc_decode_tracks_fp32():
    scn = get_scenario("siso-qpsk-r12-snr8")
    llr, state = _link_llrs(scn, 8)
    out32 = coding.decode_blocks(scn, llr)
    out8 = coding.decode_blocks(scn, llr, precision="int8")
    agree = float(jnp.mean(
        (out8["cw_llr"] > 0) == (out32["cw_llr"] > 0)
    ))
    assert agree >= 0.99, agree
    # the quantized decoder must not be worse than fp32 half a dB lower
    scn_m = scn.replace(snr_db=scn.snr_db - 0.5)
    llr_m, state_m = _link_llrs(scn_m, 8)
    bler8 = _bler(out8, state)
    bler_m = _bler(coding.decode_blocks(scn_m, llr_m), state_m)
    assert bler8 <= bler_m + 1e-9, (bler8, bler_m)


def test_ldpc_quant_pallas_matches_jnp():
    scn = get_scenario("siso-qpsk-r12-snr8")
    llr, _ = _link_llrs(scn, 2)
    out_j = coding.decode_blocks(scn, llr, precision="int8",
                                 use_pallas=False)
    out_p = coding.decode_blocks(scn, llr, precision="int8",
                                 use_pallas=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out_j["info_bits_hat"]),
        np.asarray(out_p["info_bits_hat"]),
    )
    np.testing.assert_allclose(
        np.asarray(out_j["cw_llr"]), np.asarray(out_p["cw_llr"]),
        rtol=1e-5, atol=1e-5,
    )


def test_quantized_pipeline_end_to_end():
    scn = get_scenario("siso-qam16-r12-snr15")
    pipe = scn.build(receiver="classical", precision="int8")
    assert pipe.precision == "int8"
    assert "@int8" in pipe.name
    out = pipe.run(scn.make_batch(KEY, 4))
    assert "info_bits_hat" in out
    bler = _bler(out, out)
    assert 0.0 <= bler <= 0.6


# -- tune cache: dtype-aware keys + energy objective ------------------------

def test_cache_key_distinguishes_one_byte_dtypes():
    shape = (256, 256, 256)
    k_int8 = tune.cache_key("te_gemm", shape, quant.dtype_name(jnp.int8))
    k_fp8 = tune.cache_key("te_gemm", shape, quant.dtype_name(quant.FP8_DTYPE))
    assert k_int8 != k_fp8


def test_pick_block_shape_keeps_one_byte_tunings_apart(tmp_path):
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        shape = (512, 512, 512)
        cache = tune.get_cache()
        cache.store(
            tune.cache_key("te_gemm", shape, quant.dtype_name(jnp.int8)),
            (128, 128, 128), 1.0,
        )
        cache.store(
            tune.cache_key(
                "te_gemm", shape, quant.dtype_name(quant.FP8_DTYPE)
            ),
            (256, 256, 64), 1.0,
        )
        assert te_gemm.pick_block_shape(*shape, jnp.int8) \
            == (128, 128, 128)
        assert te_gemm.pick_block_shape(*shape, quant.FP8_DTYPE) \
            == (256, 256, 64)
    finally:
        tune.set_cache_path(None)


def test_legacy_int_key_still_consulted(tmp_path):
    # old caches keyed "b{itemsize}"; the int-argument form keeps reading
    # them (fp16/bf16 collisions are benign — same width)
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        shape = (512, 512, 512)
        tune.get_cache().store(
            tune.cache_key("te_gemm", shape, "b2"), (64, 256, 128), 1.0
        )
        assert te_gemm.pick_block_shape(*shape, 2) == (64, 256, 128)
    finally:
        tune.set_cache_path(None)


def test_autotune_energy_objective_roundtrip(tmp_path):
    tune.set_cache_path(str(tmp_path / "tune.json"))
    try:
        m = n = k = 256
        shape = (m, n, k)
        best = tune.autotune_gemm(
            m, n, k, jnp.int8, iters=1, objective="energy"
        )
        key = tune.cache_key(
            "te_gemm", shape, quant.dtype_name(jnp.int8),
            objective="energy",
        )
        assert tune.get_cache().lookup(key) == tuple(best)
        # the objective-aware lookup round-trips through cached_choice
        assert tune.cached_choice(
            "te_gemm", shape, quant.dtype_name(jnp.int8),
            objective="energy",
        ) == tuple(best)
        # and latency-objective entries stay separate
        assert tune.cached_choice(
            "te_gemm", shape, quant.dtype_name(jnp.int8)
        ) is None
    finally:
        tune.set_cache_path(None)


def test_gemm_energy_fn_prefers_quantized_traffic():
    fn8 = tune.gemm_energy_fn(512, 512, 512, "int8")
    fn32 = tune.gemm_energy_fn(512, 512, 512, "fp32")
    cand = (128, 128, 128)
    assert fn8(cand, 100.0) < fn32(cand, 100.0)
