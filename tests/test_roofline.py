"""Roofline methodology validation.

Demonstrates the while-loop caveat (cost_analysis counts loop bodies once),
and validates our HLO parser against XLA's own counting on unrolled programs
— the cross-check that justifies DESIGN.md §8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hloparse import profile_hlo
from repro.analysis.roofline import build_report, model_flops_ideal
from repro.analysis.costmodel import MeshShape, hbm_traffic
from repro.configs import SHAPES, get_config


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _cost(compiled) -> dict:
    """cost_analysis() is one dict of counters."""
    ca = compiled.cost_analysis()
    assert isinstance(ca, dict), type(ca)
    return ca


def test_xla_cost_analysis_undercounts_loops():
    """The documented caveat: flops(L=2) == flops(L=8) for scanned layers."""

    def make(n):
        def f(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()
        return f

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    fl = {}
    for n in (2, 8):
        ws = jax.ShapeDtypeStruct((n, 128, 128), jnp.float32)
        fl[n] = _cost(_compile(make(n), ws, x))["flops"]
    assert fl[2] == fl[8]  # loop body counted once regardless of trip count


@pytest.mark.parametrize("n_layers", [2, 5])
def test_parser_matches_xla_on_unrolled(n_layers):
    def f(ws, x):
        h = x
        for i in range(n_layers):
            h = jnp.tanh(h @ ws[i])
        return h.sum()

    ws = jax.ShapeDtypeStruct((n_layers, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    c = _compile(f, ws, x)
    prof = profile_hlo(c.as_text())
    xla = _cost(c)["flops"]
    analytic = n_layers * 2 * 64 * 128 * 128
    assert prof.dot_flops == pytest.approx(analytic, rel=1e-6)
    assert prof.dot_flops == pytest.approx(xla, rel=0.05)


def test_parser_weights_loops_correctly():
    """Scanned and unrolled versions of the same program must agree."""

    def f_scan(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h.sum()

    def f_unroll(ws, x):
        h = x
        for i in range(6):
            h = jnp.tanh(h @ ws[i])
        return h.sum()

    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    p_scan = profile_hlo(_compile(f_scan, ws, x).as_text())
    p_unroll = profile_hlo(_compile(f_unroll, ws, x).as_text())
    assert p_scan.dot_flops == pytest.approx(p_unroll.dot_flops, rel=1e-6)


def test_build_report_bottleneck_classification():
    from repro.analysis.hloparse import HloProfile

    prof = HloProfile(dot_flops=1e12, boundary_bytes=1e9,
                      collective_wire_bytes=1e7)
    rep = build_report("x:y", "16x16", 256, prof, model_flops_global=2.56e14)
    assert rep.bottleneck == "compute"
    assert rep.compute_s > rep.memory_s
    assert 0 < rep.mfu_overlap <= 1.0 + 1e-6
    prof2 = HloProfile(dot_flops=1e9, boundary_bytes=1e12,
                       collective_wire_bytes=1e7)
    rep2 = build_report("x:y", "16x16", 256, prof2, model_flops_global=2.56e11)
    assert rep2.bottleneck == "memory"


def test_costmodel_scales_sanely():
    cfg = get_config("llama3-8b")
    mesh = MeshShape(1, 16, 16)
    tr_train = hbm_traffic(cfg, SHAPES["train_4k"], mesh)
    tr_dec = hbm_traffic(cfg, SHAPES["decode_32k"], mesh)
    # decode reads all weights once: ~ params*2B/model_shards, plus the
    # GQA-TP fallback (kv replicated over the 16-way model axis) and embed
    assert 0.9e9 < tr_dec["weights"] < 2.2e9
    # training moves far more bytes than decode
    assert tr_train["total"] > 10 * tr_dec["total"]
    # decode is dominated by weights+kv (memory-bound workload)
    assert (tr_dec["weights"] + tr_dec["kv"]) / tr_dec["total"] > 0.5


def test_model_flops_ideal():
    cfg = get_config("llama3-8b")
    mf = model_flops_ideal(cfg, SHAPES["train_4k"], 8e9)
    assert mf == pytest.approx(6 * 8e9 * 256 * 4096)
    mf_dec = model_flops_ideal(cfg, SHAPES["decode_32k"], 8e9)
    assert mf_dec == pytest.approx(2 * 8e9 * 128)
