"""The comparison that decides ``correct``, on the CPU at a small grid.

A sound run of each driver is correct; the precision control (the plain
reference computed in bfloat16 in the program's place) fails the
configuration's limits; and a run whose served step is broken underneath
comes out not correct, once for each fault a PHY cell can have: a step
that returns a stale state, half of the batch left out (its answers
copied from the other half), and an answer altered where it is made.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import checks  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import phy  # noqa: E402

BASE = json.load(open(os.path.join(BENCH, "configs", "siso-coded-fused.json")))
SECONDS = 1.0


def small_config() -> dict:
    """The SISO configuration on a 64-subcarrier grid, batch 4."""
    cfg = copy.deepcopy(BASE)
    cfg["name"] = "siso-coded-fused-test"
    cfg["grid"].update(n_subcarriers=64, fft_size=64)
    cfg["batch"] = 4
    return cfg


CLOSED = {"kind": "closed_loop", "n_cells": 2, "n_users": 4,
          "arrival_rate": 1.0, "snr_spread_db": 0.0, "init_mcs": 1,
          "deadline_ttis": 4, "buckets": [2], "warm_ticks": 3,
          "sample_share": 0.5}
BACKLOG = {"kind": "backlog", "rung": "qam16-r12", "pool_slots": 16,
           "pool_chunk": 8, "warm_batches": 1, "ref_batches": 2}


@pytest.fixture(autouse=True)
def _cache(tmp_path_factory, monkeypatch):
    d = tmp_path_factory.getbasetemp() / "jax-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(d))


def measure(traffic: dict, seed: int = 2**33 + 5, control=False) -> dict:
    import jax

    return harness.measure([], small_config(), traffic, seed, SECONDS,
                           False, jax.devices()[:1], time.perf_counter(),
                           "TPU v5 lite", control=control)


@pytest.mark.parametrize("traffic", [CLOSED, BACKLOG],
                         ids=["closed_loop", "backlog"])
def test_sound_run_is_correct(traffic):
    out = measure(traffic)
    assert out["correct"], out["compared"]
    assert out["compared"]["sampled_slots"]["value"] > 0


@pytest.mark.parametrize("traffic", [CLOSED, BACKLOG],
                         ids=["closed_loop", "backlog"])
def test_control_fails_the_limits(traffic):
    """bfloat16 in the program's place fails at least one limit, on the
    same sample as the program's answers."""
    out = measure(traffic, control=True)
    ctl, limits = out["control"], small_config()["correct"]
    assert ctl["n_slots"] == out["readings"]["n_slots"] > 0
    assert any(ctl[k] > lim for k, lim in limits.items()), ctl


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_backlog_step_is_not_correct(fault):
    with faults.planted("backlog", fault):
        out = measure(BACKLOG)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_closed_loop_step_is_not_correct(fault):
    with faults.planted("closed_loop", fault):
        out = measure(CLOSED)
    assert not out["correct"], out["compared"]


def test_reference_agrees_with_the_program_off_chip():
    """On the CPU the program's jnp path and the reference agree to
    rounding: equal CRC outcomes and iterations, LLRs within 1e-4."""
    import jax
    import slotgen
    import system
    from repro.phy import build_pipeline
    from repro.phy.scenarios import get_scenario
    from repro.serve import stack_slots

    from repro.serve.exec_registry import template_slot

    cfg = small_config()
    system.register(cfg)
    for r in phy.rungs(cfg):
        pool = slotgen.make_pool(r, jax.random.PRNGKey(11), 4, 4)
        scn = get_scenario(system.scenario_name(cfg, r.name))
        # the program caches its data-RE index on first use; a first use
        # inside a trace would cache a tracer, so make it eagerly here
        template_slot(scn)
        pipe = build_pipeline("classical", scn,
                              **system.receiver_options(cfg))
        st = jax.device_get(pipe.run(stack_slots(pool, 0)))
        ref = checks.pool_reference(
            np.concatenate([s["y_time"] for s in pool]), r)
        gap = np.linalg.norm(st["cw_llr"] - ref["cw_llr"]) / np.linalg.norm(
            ref["cw_llr"])
        assert gap < 1e-4
        assert np.array_equal(st["crc_ok"], ref["crc_ok"])
        assert np.array_equal(st["decode_iters"], ref["iters"])
