"""The served path's Pallas kernels compile for a TPU v5e (Mosaic).

Interpret mode cannot see what the chip's compiler refuses: block shapes
off the (8, 128) tiling, zero-length slices, reductions Mosaic does not
lower.  These tests compile — they never run — each main-path kernel at
real widths (256 subcarriers, 14 symbols, batch 8) against a *described*
``v5e:2x2`` topology, and check that the compiled program holds a Mosaic
kernel (``tpu_custom_call``).

The topology and everything built from it live in module-scoped fixtures,
never at import time: only one process may load the TPU library, so a
describe at collection would break the other test workers.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ldpc, rx_fused
from repro.phy import link
from repro.phy.scenarios import get_scenario
from repro.serve.exec_registry import template_batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Route kernel choice as a TPU backend would: Pallas, never
    interpreted (the CPU backend the tests run on picks the jnp twins)."""
    for mod in (ldpc, rx_fused):
        monkeypatch.setattr(mod, "_use_pallas", lambda use_pallas: True)
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_kernels(fn, *specs) -> list[str]:
    """Compile ``fn`` for the described chip; the op names of its
    Mosaic kernels."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    return [
        line.split('op_name="', 1)[1].split('"', 1)[0]
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("lanes", [18, 144])
def test_ldpc_decoder_compiles(one_chip, precision, lanes):
    """Codeword counts of one qam16-r12 slot (18) and of a batch-8 slot
    bucket (144): neither is a multiple of the 128-lane tile."""
    code = get_scenario("siso-qam16-r12-snr15").code
    llr = _spec((lanes, code.n_mother), jnp.float32, one_chip)
    names = _mosaic_kernels(
        lambda l: ldpc.ldpc_decode_pallas(
            l, code, interpret=False, precision=precision
        ),
        llr,
    )
    assert any("ldpc_decode" in n for n in names), names


def _rx_specs(scn, one_chip, batch=8):
    g = scn.grid
    y = _spec((batch, g.n_symbols, g.n_subcarriers, g.n_rx), jnp.complex64,
              one_chip)
    h = _spec((batch, g.n_subcarriers, g.n_rx, g.n_tx), jnp.complex64,
              one_chip)
    nv = _spec((), jnp.float32, one_chip)
    return y, h, nv


@pytest.mark.parametrize("scenario", ["siso-qam16-r12-snr15",
                                      "mimo4x8-qam16-snr12"])
def test_mmse_detect_demap_compiles(one_chip, scenario):
    scn = get_scenario(scenario)
    names = _mosaic_kernels(
        lambda y, h, nv: rx_fused.mmse_detect_demap_pallas(
            y, h, nv, scn.modem, interpret=False
        ),
        *_rx_specs(scn, one_chip),
    )
    assert any("rx_detect_demap" in n for n in names), names


def test_sic_detect_demap_compiles(one_chip):
    scn = get_scenario("mimo4x4-qam16-mu-snr18")
    names = _mosaic_kernels(
        lambda y, h, nv: rx_fused.sic_detect_demap_pallas(
            y, h, nv, scn.modem, interpret=False
        ),
        *_rx_specs(scn, one_chip),
    )
    assert any("rx_sic_demap" in n for n in names), names


def test_ls_che_compiles(one_chip):
    scn = get_scenario("siso-qam16-r12-snr15")
    g = scn.grid
    op = rx_fused.make_ls_interp_operator(
        g.n_subcarriers, g.n_tx, g.pilot_stride,
        np.exp(1j * np.pi / 4 * np.arange(g.n_subcarriers)),
    )
    y, _, _ = _rx_specs(scn, one_chip)
    opspec = dataclasses.replace(
        op, band=_spec(op.band.shape, jnp.complex64, one_chip))
    names = _mosaic_kernels(
        lambda y, op: rx_fused.ls_che_pallas(
            y, g.pilot_symbols, g.pilot_stride, op, interpret=False
        ),
        y, opspec,
    )
    assert any("rx_ls_che" in n for n in names), names


def _nr100(one_chip):
    """A 100 MHz carrier (3276 subcarriers, not a multiple of 128) into 4
    receive antennas, batch 8: the grid of ``nr100-simo1x4-fused``."""
    scn = get_scenario("siso-qam16-r12-snr15")
    g = dataclasses.replace(scn.grid, n_subcarriers=3276, fft_size=4096,
                            n_rx=4)
    scn = scn.replace(grid=g)
    return scn, _rx_specs(scn, one_chip)


def test_ls_che_compiles_at_100mhz(one_chip):
    """The tiled LS-CHE kernel at 3276 x 4 rx.  The parent's kernel took
    the whole operator (43 MB) as one block: it compiled alone, and ran
    out of scoped VMEM inside the whole served step."""
    scn, (y, _, _) = _nr100(one_chip)
    g = scn.grid
    op = rx_fused.make_ls_interp_operator(
        g.n_subcarriers, g.n_tx, g.pilot_stride,
        np.exp(1j * np.pi / 4 * np.arange(g.n_subcarriers)),
    )
    names = _mosaic_kernels(
        lambda y: rx_fused.ls_che_pallas(
            y, g.pilot_symbols, g.pilot_stride, op, interpret=False
        ),
        y,
    )
    assert any("rx_ls_che" in n for n in names), names


def test_mmse_detect_demap_compiles_at_100mhz(one_chip):
    scn, specs = _nr100(one_chip)
    names = _mosaic_kernels(
        lambda y, h, nv: rx_fused.mmse_detect_demap_pallas(
            y, h, nv, scn.modem, interpret=False
        ),
        *specs,
    )
    assert any("rx_detect_demap" in n for n in names), names


@pytest.mark.parametrize("scenario,options,kernels", [
    ("siso-qam16-r12-snr15", {"fused": True},
     ("rx_ls_che", "rx_detect_demap", "ldpc_decode")),
    ("mimo4x4-qam16-mu-snr18", {"sic": True},
     ("rx_sic_demap", "ldpc_decode")),
], ids=["siso-fused", "mu-sic"])
def test_served_mesh_step_compiles(one_chip, on_chip, scenario, options,
                                   kernels):
    """The closed-loop mesh step as served: ``vmap(pipeline._apply)``
    over 4 lanes of batch-8 HARQ slots (rv + prior_llr)."""
    scn = get_scenario(scenario)
    pipe = link.build_pipeline("classical", scn, **options)
    lane = template_batch(scn, 8, harq=True)
    staged = {
        k: _spec((4,) + np.shape(v), np.asarray(v).dtype, one_chip)
        for k, v in lane.items()
    }
    names = _mosaic_kernels(jax.vmap(pipe._apply), staged)
    for k in kernels:
        assert any(k in n for n in names), (k, names)
