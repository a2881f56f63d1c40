"""The serving loop's ``serve.*`` spans, read back from a profiler trace.

One tick of a small ``MeshSlotScheduler`` and one ``PhyServeEngine.run``
are profiled on the CPU and the trace is reduced as the benchmark
reduces a chip trace (``bench/xtrace.py``, ``bench/spans.py``): the span
tree, and the spans' counts against the program's own counters.
"""
import dataclasses
import os
import sys

import jax
import pytest

from repro.phy.scenarios import (
    MCSLadder, get_ladder, get_scenario, register_ladder, register_scenario,
)
from repro.serve import MeshSlotScheduler, PhyServeEngine

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench"))

import spans  # noqa: E402
import xtrace  # noqa: E402

_SMOKE = dict(n_subcarriers=64, fft_size=64, n_taps=4, delay_spread=1.0)


def _small(name: str, new: str):
    try:
        return get_scenario(new)
    except KeyError:
        pass
    s = get_scenario(name).replace(name=new)
    s = s.replace(grid=dataclasses.replace(s.grid, **_SMOKE))
    return register_scenario(s)


def _ladder() -> str:
    _small("siso-qpsk-r12-snr8", "trc-qpsk-r12")
    _small("siso-qam16-r12-snr15", "trc-qam16-r12")
    try:
        get_ladder("trc-siso")
    except KeyError:
        register_ladder(MCSLadder("trc-siso", ("trc-qpsk-r12",
                                               "trc-qam16-r12")))
    return "trc-siso"


def profiled(fn, log_dir: str):
    """``fn()`` inside a ``window`` span of a profiler trace; its result
    and the trace reduced by both reductions."""
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, spans.load(log_dir), xtrace.load(log_dir)


def parent(sp, s):
    """The innermost other ``serve.*`` span around span ``s``."""
    around = [o for o in sp.spans if o is not s and o.start <= s.start
              and s.end <= o.end]
    return min(around, key=lambda o: o.dur).name if around else None


@pytest.fixture(scope="module")
def mesh_tick(tmp_path_factory):
    # 3 cells on a mesh of one: 3 lanes a step pad to a bucket of 4
    sch = MeshSlotScheduler.uniform(
        _ladder(), 3, n_users=2, arrival_rate=0.0, batch_size=2,
        max_retx=1, seed=3,
    )
    sch.inject_backlog(1)
    filler0 = sch.n_filler_lanes
    stats, sp, summary = profiled(
        sch.tick, str(tmp_path_factory.mktemp("tick")))
    return sch, stats, sp, summary, sch.n_filler_lanes - filler0


def test_tick_span_tree(mesh_tick):
    _, _, sp, _, _ = mesh_tick
    (tick,) = sp.named("serve.tick")
    assert parent(sp, tick) is None
    for name in ("serve.arrive", "serve.rebalance", "serve.plan",
                 "serve.dispatch", "serve.end_tick"):
        assert [parent(sp, s) for s in sp.named(name)] == ["serve.tick"]
    builds = sp.named("serve.make_slot")
    assert builds and {parent(sp, s) for s in builds} == {"serve.plan"}
    waits = sp.named("serve.wait")
    assert waits and {parent(sp, s) for s in waits} == {"serve.dispatch"}
    assert {parent(sp, s) for s in sp.named("serve.feedback")} == {
        "serve.tick"}
    assert {parent(sp, s) for s in sp.named("serve.stage")} <= {
        "serve.tick", "serve.dispatch"}


def test_one_slot_build_per_planned_slot(mesh_tick):
    sch, stats, sp, _, _ = mesh_tick
    served = sum(st.n_served for st in stats)
    assert served == 6  # 3 cells x 2 users, one job each
    assert len(sp.named("serve.make_slot")) == served
    assert sp.count("serve.tick", "slots") == served
    assert sp.count("serve.make_slot", "retx") == 0
    # every slot built by a slot-generator executable prebuilt at set-up
    assert sp.count("serve.make_slot", "compiled") == served
    assert sp.count("serve.plan", "batches") == sum(
        loop.n_batches for loop in sch.loops)
    assert 0 < spans.numbers(sp)["eager_ops_per_slot"] <= 3


def test_lane_counts_match_the_filler_lanes(mesh_tick):
    _, _, sp, _, filler = mesh_tick
    steps = sp.named("serve.dispatch")
    assert filler == 1
    assert sum(s.stats["bucket"] - s.stats["lanes"] for s in steps) == filler
    assert [(s.stats["lanes"], s.stats["bucket"]) for s in steps] == [
        (s.stats["lanes"], s.stats["bucket"])
        for s in sp.named("serve.stage")]
    assert sum(s.stats["lanes"] for s in sp.named("serve.feedback")) == 3


def test_tick_time_is_covered_by_its_phases(mesh_tick):
    _, _, sp, summary, _ = mesh_tick
    # the CPU backend has no device plane: no idle time to label
    assert sp.idle_by_span == summary.idle_by_span == {}
    assert spans.coverage(sp)["tick_self_share"] < 5.0
    got = spans.numbers(sp)
    assert got["tti_host_ms"] > 0 and got["slot_build_ms_per_tti"] > 0
    assert got["batch_host_us_per_slot"] is None


@pytest.mark.parametrize("supervised", [False, True])
def test_engine_run_spans(tmp_path, supervised):
    scn = _small("siso-qpsk-r12-snr8", "trc-qpsk-r12")
    eng = PhyServeEngine.from_scenario(scn, batch_size=2,
                                       supervised=supervised)
    eng.submit_traffic(jax.random.PRNGKey(0), 5)
    eng.run()  # acquires the executable outside the trace
    eng.submit_traffic(jax.random.PRNGKey(1), 5)
    rep, sp, _ = profiled(eng.run, str(tmp_path))
    batches = sp.named("serve.batch")
    assert len(batches) == rep.n_batches == 3
    assert sp.count("serve.batch", "slots") == rep.n_slots == 5
    for name in ("serve.stack", "serve.dispatch", "serve.slot_metrics"):
        assert [parent(sp, s) for s in sp.named(name)] == \
            ["serve.batch"] * 3
    assert {parent(sp, s) for s in sp.named("serve.wait")} == {
        "serve.dispatch"}
    assert len(sp.named("serve.report")) == 1
    # the dispatch spans are the timed window the report's wall time sums
    assert 0 <= rep.wall_s - sp.total_s("serve.dispatch") < 1e-3
    assert sp.named("serve.acquire") == []  # resident: no build
    got = spans.numbers(sp)
    assert got["batch_host_us_per_slot"] > 0
    assert got["stack_us_per_slot"] > 0
    assert got["tti_host_ms"] is None


def test_spans_carry_the_step_width(mesh_tick, tmp_path):
    """``serve.batch`` and ``serve.dispatch`` name the width they served
    (subcarriers and code blocks a slot), so a trace splits by it."""
    from repro.phy import coding

    scn = _small("siso-qam16-r12-snr15", "trc-qam16-r12")
    width = {"n_sc": 64, "codewords": coding.codewords_per_slot(scn)}
    eng = PhyServeEngine.from_scenario(scn, batch_size=2)
    eng.submit_traffic(jax.random.PRNGKey(0), 3)
    eng.run()
    eng.submit_traffic(jax.random.PRNGKey(1), 3)
    _, sp, _ = profiled(eng.run, str(tmp_path))
    for name in ("serve.batch", "serve.dispatch"):
        got = [{k: s.stats[k] for k in width} for s in sp.named(name)]
        assert got == [width] * 2, name
    _, _, tick, _, _ = mesh_tick
    steps = tick.named("serve.dispatch")
    ladder = [get_scenario(n) for n in get_ladder("trc-siso").rungs]
    assert steps and all(
        s.stats["n_sc"] == 64 and s.stats["codewords"]
        == coding.codewords_per_slot(ladder[s.stats["mcs"]]) for s in steps)
