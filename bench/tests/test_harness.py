"""CPU tests of the benchmark's pure parts: trace reduction, operation
and byte counts, metric arithmetic, the agreement of the benchmark's
PHY tables with the program's, and the refusal to run without a TPU.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import json
import math
import os
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import drivers  # noqa: E402
import ops  # noqa: E402
import phy  # noqa: E402
import xtrace  # noqa: E402

SISO = json.load(open(os.path.join(BENCH, "configs", "siso-coded-fused.json")))
MU = json.load(open(os.path.join(BENCH, "configs", "mu-mimo4x4-sic.json")))

# device: two ops overlapping on [1, 5) us, one on [12, 13) us outside
# every span but the window; host: window [0, 20) us, tick [4, 10) us
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000
             stats { metadata_id: 1 str_value: "jit(step)/ldpc_decode" } }
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "tick" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return xtrace.reduce(ProfileData.from_text_proto(TRACE))


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_busy_is_the_union_of_device_ops(summary):
    assert summary.window_s == pytest.approx(20e-6)
    assert summary.busy_s == pytest.approx(5e-6)
    assert summary.n_devices == 1


def test_kernel_time_by_name(summary):
    assert summary.kernel_s("ldpc_decode") == pytest.approx(3e-6)
    assert summary.kernel_s("rx_detect_demap") == 0.0
    assert dict(summary.top_ops()) == {"custom-call": pytest.approx(3e-6),
                                       "fusion": pytest.approx(3e-6)}


@pytest.mark.parametrize("name,kind", [
    ('%ldpc_decode.1 = (f32[24,32,256]) custom-call(f32[24,32,256] %pad.0)'
     ', custom_call_target="tpu_custom_call"', "ldpc_decode"),
    ('%custom-call.13 = (f32[256,128]) custom-call(f32[256,128] %s), '
     'custom_call_target="LuDecompositionBlock"', "LuDecompositionBlock"),
    ("%fusion.12 = f32[3456,8,4] fusion(f32[8,14,256,4] %copy.105)",
     "fusion"),
    ("copy.107", "copy"),
])
def test_op_kinds(name, kind):
    assert xtrace.short_name(name) == kind


def test_idle_gaps_by_host_span(summary):
    # gaps [0,1) and [13,20) sit in the window only, [5,12) in the tick
    idle = dict(summary.top_idle())
    assert idle["window"] == pytest.approx(8e-6)
    assert idle["tick"] == pytest.approx(7e-6)
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_ops_hand_worked_siso_qam16_r12():
    r = phy.rung(SISO, "qam16-r12")
    # 12 data symbols x 300 + 2 DMRS symbols x 150 REs, 4 bits each
    assert r.data_bits == 15600
    assert (r.codewords, r.info_bits_per_slot) == (20, 7360)
    assert ops.fft(r) == 14 * 5 * 300 * math.log2(300)
    assert ops.ls_che(r, fused=True) == 2 * 2 * 150 + 8 * 150 * 300
    assert ops.smooth(r) == 8 * 300 * 300
    assert ops.detect_demap(r) == 14 * 300 * (8 * 3 + 4 * 8)
    # 36 protograph edges + 23 dual-diagonal ones, 20 codewords, z = 32
    assert ops.ldpc_iteration(r) == 20 * 59 * 32 * 10


def test_ops_hand_worked_mu_sic():
    r = phy.rung(MU, "qam16-r12")
    # four ports fill both DMRS symbols: 12 x 300 REs, 4 streams, 4 bits
    assert r.data_bits == 57600
    assert (r.codewords, r.info_bits_per_slot) == (75, 27600)
    solve = sum(8 * (m * m * 4 + m ** 3 + m * 4) for m in range(1, 5))
    assert ops.detect_demap(r) == 14 * 300 * (solve + 4 * 4 * 8 + 3 * 8 * 4)
    assert ops.ls_che(r, fused=False) == 2 * 300 * 4 * 10 + 300 * 16 * 8


def test_ladder_sizes():
    got = [(r.codewords, r.info_bits_per_slot) for r in phy.rungs(SISO)]
    assert got == [(10, 3680), (20, 7360), (30, 11040)]


def _run(window, **kw):
    ns = dict(window=window, setup_s=12.5, config=SISO, fused=True,
              rungs={r.name: r for r in phy.rungs(SISO)}, n_chips=1,
              peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    ns.update(kw)
    return types.SimpleNamespace(**ns)


def _read(name, run):
    import importlib

    return importlib.import_module(f"metrics.{name}").read(run)


def test_end_to_end_arithmetic():
    w = drivers.Window("closed_loop")
    w.window_s, w.slots, w.info_bits_ok = 2.0, 500, 3e6
    w.tick_s = [0.1 * i for i in range(1, 11)]  # 0.1 .. 1.0 s
    w.dispatch_s = [0.01] * 10
    run = _run(w)
    assert _read("slots_per_s", run) == 250.0
    assert _read("goodput_mbps", run) == 1.5
    # numpy's linear percentile: 0.9 + 0.1 * 0.1 = 0.91 s
    assert _read("tti_p90_ms", run) == pytest.approx(910.0)
    assert _read("host_ms_per_tti", run) == pytest.approx(540.0)
    assert _read("setup_s", run) == 12.5


def test_per_layer_arithmetic(summary):
    w = drivers.Window("closed_loop")
    w.window_s, w.slots = 1.0, 100
    w.slots_by_rung = {"qam16-r12": 100}
    w.lanes_staged, w.filler_lanes, w.compiles = 16, 4, 0
    w.trace = summary
    run = _run(w)
    r = phy.rung(SISO, "qam16-r12")
    assert _read("filler_lane_share", run) == 25.0
    assert _read("compiles_in_window", run) == 0.0
    assert _read("device_idle_share", run) == pytest.approx(75.0)
    assert _read("rx_mfu", run) == pytest.approx(
        100 * 100 * ops.per_slot(r, True) / 197e12)
    assert _read("ldpc_decode_us_per_slot", run) == pytest.approx(0.03)
    # no rx_detect_demap event in the trace: the reader finds nothing
    assert _read("rx_detect_demap_us_per_slot", run) is None
    assert _read("rx_sic_demap_us_per_slot", run) is None


def test_seed_words_take_large_seeds():
    a = drivers.seed_words(2**40 + 3, "pool", 2)
    assert a == drivers.seed_words(2**40 + 3, "pool", 2)
    assert a != drivers.seed_words(2**40 + 4, "pool", 2)
    assert all(0 <= x < 2**31 for x in a)


def test_phy_tables_match_the_program():
    """The benchmark's own code tables and grid agree with the program's,
    so the generator's slots decode and the reference decodes alike."""
    from repro.phy import coding, ofdm

    for cfg in (SISO, MU):
        for r in phy.rungs(cfg):
            rate = next(x["rate"] for x in cfg["rungs"] if x["name"] == r.name)
            code = coding.make_code(rate)
            assert r.code.layers() == code.layers()
            assert r.code.e_bits == code.e_bits
            assert np.array_equal(
                phy.crc_matrix(code.k_info, 0x1021, 16),
                coding.crc_matrix(code.k_info))
            g = ofdm.GridConfig(
                n_subcarriers=r.n_sc, fft_size=r.fft_size,
                pilot_stride=r.pilot_stride,
                pilot_symbols=r.pilot_symbols, n_tx=r.n_tx, n_rx=r.n_rx)
            assert np.array_equal(r.pilot_masks(),
                                  ofdm.link_pilot_masks_np(g))
            assert np.allclose(r.pilot_seq(),
                               np.asarray(ofdm.pilot_sequence(g)))


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "siso-backlog", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_reduction_of_a_recorded_chip_trace():
    """A 0.3 s backlog window of `siso-backlog` recorded on one TPU v5
    lite: the reduction finds the window, the device and the kernels."""
    import gzip

    from jax.profiler import ProfileData

    path = os.path.join(BENCH, "tests", "data", "siso-backlog.xplane.pb.gz")
    with gzip.open(path) as f:
        s = xtrace.reduce(ProfileData.from_serialized_xspace(f.read()))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.3126, rel=1e-3)
    assert 0 < s.busy_s < 0.05 * s.window_s
    assert s.kernel_s("ldpc_decode") == pytest.approx(2.41588e-4, rel=1e-4)
    assert s.kernel_s("rx_detect_demap") > 0
    assert s.kernel_s("rx_sic_demap") == 0
    assert sum(v for _, v in s.top_idle()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.top_ops()[0][0] == "LuDecompositionBlock"
