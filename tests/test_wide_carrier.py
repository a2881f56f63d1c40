"""A 100 MHz NR carrier (273 PRB, 3276 subcarriers, 4 receive antennas)
through the fused classical receiver, checked on the CPU.

* The tiled LS-CHE kernel (Pallas in interpret mode, and its jnp route)
  gives the dense operator's ``h_ls`` at widths that span several tiles
  and are not multiples of 128.
* Its VMEM a grid step is set by the tile shapes, not by ``n_sc``.
* The LS-CHE roofline counts of the benchmark, worked by hand, and the
  readers of the ``nr100-backlog`` cell's per-layer metrics.
* The ``nr100-simo1x4-fused`` receive chain, cut to 96 subcarriers,
  agrees with the benchmark's plain reference within the configuration's
  limits of ``correct``.
"""
import copy
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import rx_fused

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

import che_roofline  # noqa: E402
import checks  # noqa: E402
import drivers  # noqa: E402
import harness  # noqa: E402
import phy  # noqa: E402
import slotgen  # noqa: E402
import system  # noqa: E402
import xtrace  # noqa: E402

NR100 = json.load(open(os.path.join(BENCH, "configs",
                                    "nr100-simo1x4-fused.json")))
PILOT_SYMBOLS = (2, 11)
MiB = 1 << 20
VMEM_BUDGET = 8 * MiB  # a quarter of a v5e core's VMEM, whatever n_sc


def _qpsk(n: int, seed: int) -> np.ndarray:
    k = np.random.default_rng(seed).integers(0, 4, n)
    return np.exp(1j * (np.pi / 4 + np.pi / 2 * k)).astype(np.complex64)


def _grid(b: int, n_sc: int, n_rx: int, seed: int = 0) -> jax.Array:
    kr, ki = jax.random.split(jax.random.PRNGKey(seed))
    shape = (b, 14, n_sc, n_rx)
    return (jax.random.normal(kr, shape)
            + 1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


# (n_sc, n_tx): several tiles, none a multiple of 128 (3276 is the
# 100 MHz carrier); n_tx 4 needs a multiple of its comb spacing 8
@pytest.mark.parametrize("n_sc,n_tx", [
    (300, 1), (780, 1), (3276, 1), (296, 4), (776, 4),
])
@pytest.mark.parametrize("route", ["pallas", "jnp"])
def test_tiled_ls_che_matches_the_dense_operator(n_sc, n_tx, route):
    seq = _qpsk(n_sc, n_sc)
    op = rx_fused.make_ls_interp_operator(n_sc, n_tx, 2, seq)
    assert op.n_tiles >= 2 and op.band.shape[-1] % 128 == 0
    y = _grid(2, n_sc, 4, seed=n_tx)
    want = rx_fused.ls_che_jnp(y, PILOT_SYMBOLS, 2, op.dense(seq))
    if route == "pallas":
        got = rx_fused.ls_che_pallas(y, PILOT_SYMBOLS, 2, op,
                                     interpret=True)
    else:
        got = rx_fused.ls_che(y, PILOT_SYMBOLS, 2, op, use_pallas=False)
    assert got.shape == (2, n_sc, 4, n_tx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _blocks_of_ls_che(monkeypatch, n_sc: int, n_tx: int) -> list:
    """Block shapes of every operand of the ``rx_ls_che`` call at batch 8
    x 4 receive antennas, traced and not run."""
    seen = []
    real = rx_fused.pl.pallas_call

    def spy(kernel, **kw):
        seen.extend(s.block_shape for s in kw["in_specs"])
        seen.append(kw["out_specs"].block_shape)
        return real(kernel, **kw)

    monkeypatch.setattr(rx_fused.pl, "pallas_call", spy)
    op = rx_fused.make_ls_interp_operator(n_sc, n_tx, 2, _qpsk(n_sc, 1))
    y = jax.ShapeDtypeStruct((8, 14, n_sc, 4), jnp.complex64)
    jax.eval_shape(lambda y: rx_fused.ls_che_pallas(
        y, PILOT_SYMBOLS, 2, op, interpret=True), y)
    return seen, op


@pytest.mark.parametrize("n_tx,widths", [(1, (300, 3276, 13104)),
                                         (4, (296, 3272, 13104))])
def test_ls_che_vmem_does_not_grow_with_the_carrier(monkeypatch, n_tx,
                                                    widths):
    """The VMEM of one grid step, from the kernel's own block shapes, is
    what ``ls_che_vmem_bytes`` says, stays under the budget, and is the
    same at 5 MHz, 100 MHz and four times that (the parent's whole-
    operator block was 43 MB at 3276)."""
    got = []
    for n_sc in widths:
        blocks, op = _blocks_of_ls_che(monkeypatch, n_sc, n_tx)
        pad = lambda s: (s[:-2] + (-(-s[-2] // 8) * 8, -(-s[-1] // 128) * 128))
        from_blocks = 2 * 4 * sum(int(np.prod(pad(tuple(b)))) for b in blocks)
        v = rx_fused.ls_che_vmem_bytes(n_tx, 2, 32, op.block_sc, op.width)
        assert v == from_blocks
        assert v <= VMEM_BUDGET
        got.append(v)
    assert len(set(got)) == 1, got


def test_che_roofline_hand_worked():
    r = phy.rung(NR100, "qam16-r12")
    # 2 pilot symbols x 1638 comb REs; 2 ops each per rx, then 2 complex
    # MACs (16 ops) per subcarrier, rx and tx
    assert che_roofline.pilot_res(r) == 3276
    assert che_roofline.ops_per_slot(r) == 2 * 3276 * 4 + 16 * 3276 * 4
    # pilot REs in, h_ls out, complex64
    assert che_roofline.bytes_per_slot(r) == 8 * (3276 * 4 + 3276 * 4)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert che_roofline.roofline_s_per_slot(r, peaks) == pytest.approx(
        209664 / 819e9)


def _ev(name: str, start_us: float, dur_us: float, text: str = ""):
    return xtrace.Op(start_us * 1e3, (start_us + dur_us) * 1e3, name,
                     name + ("|" + text if text else ""))


def test_nr100_layer_readers():
    """The kernel's own operations count, not an operation that reads its
    output; the Wiener solve is its LU and diagonal-block inverses."""
    ops = [
        _ev("%rx_ls_che.1 = f32[2,32,3328] custom-call(...)", 0, 4.0),
        _ev("%fusion.20 = f32[32,3276] fusion(%rx_ls_che.1)", 5, 30.0),
        _ev("%custom-call.13 = (...) custom-call(...), "
            'custom_call_target="LuDecompositionBlock"', 40, 100.0),
        _ev("%custom-call.14 = f32[...]", 150, 20.0,
            "InvertDiagBlocksLowerTriangular"),
        _ev("%ldpc_decode.1 = (...) custom-call(...)", 200, 50.0),
    ]
    trace = xtrace.Summary(1.0, 0.0002, 1, {}, {}, ops)
    w = drivers.Window("backlog")
    w.window_s, w.slots = 1.0, 8
    w.slots_by_rung = {"qam16-r12": 8}
    w.trace = trace
    run = types.SimpleNamespace(
        window=w, rungs={r.name: r for r in phy.rungs(NR100)},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    read = lambda m: harness.reader(m)(run)
    assert read("rx_ls_che_us_per_slot") == pytest.approx(0.5)
    assert read("rx_ls_che_roofline") == pytest.approx(
        100 * 8 * 209664 / 819e9 / 4e-6)
    assert read("wiener_solve_us_per_slot") == pytest.approx(15.0)
    w.trace = None
    for m in ("rx_ls_che_us_per_slot", "rx_ls_che_roofline",
              "wiener_solve_us_per_slot"):
        assert read(m) is None


def test_nr100_chain_matches_the_reference_at_96_subcarriers():
    """The configuration's fused 1x4 chain, cut to 96 subcarriers, on 8
    seeded slots of ``slotgen``: within its limits of ``correct``."""
    from repro.phy import build_pipeline
    from repro.phy.scenarios import get_scenario
    from repro.serve import stack_slots

    cfg = copy.deepcopy(NR100)
    cfg["name"] = "nr100-simo1x4-fused-test"
    cfg["grid"]["n_subcarriers"] = 96
    system.register(cfg)
    r = phy.rung(cfg, "qam16-r12")
    scn = get_scenario(system.scenario_name(cfg, r.name))
    pipe = build_pipeline(cfg["receiver"]["kind"], scn,
                          **system.receiver_options(cfg))
    pool = slotgen.make_pool(r, jax.random.PRNGKey(2**31 + 11), 8, 8)
    state = pipe.run(stack_slots(pool))
    ref = checks.pool_reference(
        np.concatenate([s["y_time"] for s in pool]), r)
    idx = list(range(8))
    got = checks.backlog(
        [(idx, np.asarray(state["cw_llr"]))],
        [(idx, np.asarray(state["crc_ok"]),
          np.asarray(state["decode_iters"]))], ref)
    assert got["n_blocks"] == 8 * r.codewords == 48
    for k, limit in cfg["correct"].items():
        assert got[k] <= limit, (k, got)
