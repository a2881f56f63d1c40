"""The closed loop's compiled slot generator (:class:`SlotGenerator`).

Each (re)transmission slot is one call of an AOT executable built from
the body of :func:`repro.phy.coding.make_coded_slot`; the eager function
stays the reference.  Same key seed, SNR, RV and transport blocks must
give the same slot, and a scheduler must build every executable it uses
before its first tick: at most ``1 + max_retx`` per rung, whatever the
users' SNRs.
"""
import collections
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.phy import coding
from repro.phy.scenarios import (
    MCSLadder, get_ladder, get_scenario, register_ladder, register_scenario,
)
from repro.serve import ExecRegistry, MeshSlotScheduler, SlotScheduler
from repro.serve.runtime import SlotGenerator, resolve_ladder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import system  # noqa: E402


def _fused_rungs() -> list:
    with open(os.path.join(ROOT, "bench", "configs",
                           "siso-coded-fused.json")) as f:
        config = json.load(f)
    return resolve_ladder(system.register(config))[1]


def _case(name: str):
    """Each rung of the benchmark's ``siso-coded-fused`` ladder, and its
    16-QAM rung with two co-channel interferers on an aging channel."""
    rungs = {r.name.rsplit(".", 1)[1]: r for r in _fused_rungs()}
    if name != "intf":
        return rungs[name]
    return rungs["qam16-r12"].replace(interferer_db=(-6.0, -10.0),
                                      doppler_rho=0.95)


def _assert_same_slot(got: dict, want: dict) -> None:
    assert set(got) == set(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert (a.shape, a.dtype) == (b.shape, b.dtype), k
        if k in ("bits", "info_bits", "rv") or a.dtype.kind in "biu":
            np.testing.assert_array_equal(a, b, err_msg=k)
        else:
            # relative to the array's scale as well: an IFFT output near
            # zero carries the absolute rounding of its largest terms
            np.testing.assert_allclose(a, b, rtol=1e-5,
                                       atol=1e-5 * np.abs(b).max(),
                                       err_msg=k)


@pytest.mark.parametrize("name", ["qpsk-r12", "qam16-r12", "qam16-r34",
                                  "intf"])
def test_compiled_generator_matches_eager_slot(name):
    scn = _case(name)
    scn = scn.replace(snr_db=scn.snr_db + 0.37)  # not exact in float32
    gen = SlotGenerator(registry=ExecRegistry(persistent=False))
    seed = 2**31 - 2

    slot, _ = gen(scn, seed)
    want = coding.make_coded_slot(jax.random.PRNGKey(seed), scn, 1, rv=0)
    _assert_same_slot(slot, want)

    info = np.asarray(want["info_bits"])
    slot, _ = gen(scn, 12345, rv=2, info=info)
    want = coding.make_coded_slot(jax.random.PRNGKey(12345), scn, 1, rv=2,
                                  info=info)
    _assert_same_slot(slot, want)


_SMOKE = dict(n_subcarriers=64, fft_size=64, n_taps=4, delay_spread=1.0)


def _ladder() -> str:
    """A two-rung small-grid ladder at an SNR low enough to NACK."""
    for base, new in (("siso-qpsk-r12-snr8", "sg-qpsk-r12"),
                      ("siso-qam16-r12-snr15", "sg-qam16-r12")):
        try:
            get_scenario(new)
        except KeyError:
            s = get_scenario(base).replace(name=new)
            register_scenario(s.replace(
                grid=dataclasses.replace(s.grid, **_SMOKE)))
    try:
        get_ladder("sg-siso")
    except KeyError:
        register_ladder(MCSLadder("sg-siso", ("sg-qpsk-r12",
                                              "sg-qam16-r12")))
    return "sg-siso"


def _slot_execs(reg: ExecRegistry) -> list:
    return [k for k in reg.keys() if k.scenario.startswith("coded_slot:")]


@pytest.mark.parametrize("mesh", [False, True])
def test_scheduler_builds_slot_generators_before_the_first_tick(mesh):
    reg = ExecRegistry(persistent=False)
    max_retx = 2
    kw = dict(n_users=4, arrival_rate=1.0, snr_db=3.0, snr_spread_db=2.0,
              batch_size=2, max_retx=max_retx, seed=5, registry=reg)
    if mesh:
        sch = MeshSlotScheduler.uniform(_ladder(), 2, **kw)
        loops = sch.loops
    else:
        sch = SlotScheduler(_ladder(), **kw)
        loops = [sch.loop]
    snrs = {u.snr_db for loop in loops for u in loop.users}
    assert len(snrs) == sum(len(loop.users) for loop in loops)
    built = _slot_execs(reg)
    per_rung = collections.Counter((k.scenario, k.variant) for k in built)
    assert len(per_rung) == len(loops[0].rungs)
    assert max(per_rung.values()) <= 1 + max_retx
    rep = sch.run(4)
    assert rep.mean_harq_rounds > 1.0  # retransmissions were sent
    assert _slot_execs(reg) == built
