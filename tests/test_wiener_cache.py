"""The Wiener filter is built once per noise variance, outside the served
step, and handed to it as the slot's ``wiener_w``.

* A served step given its filter by the cache answers as the step that
  builds the filter in its own graph: the same LLRs and CRCs.
* A mesh step's lanes each get the filter of their own noise variance,
  in the closed loop and in the open-loop mesh engine.
* The cache builds once per distinct float32 noise variance and then only
  hits; its byte bound evicts the least recently used filter, and counts
  it.  An engine builds its filter while it warms up, before serving.
* The compiled served step holds nothing of the build: no linear solve,
  no ``n_sc x n_sc`` constant; the builder holds no such constant either.
* A donated step leaves the cached filter alive (the filter is not an
  output of the step).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.phy import build_pipeline, classical
from repro.phy.scenarios import (
    MCSLadder, get_ladder, get_scenario, register_ladder, register_scenario,
)
from repro.serve import (
    BatchRunner, CellMeshEngine, ExecRegistry, MeshSlotScheduler,
    PhyServeEngine, SlotRequest, cell, closed_cell, make_traffic,
    stack_slots, template_batch,
)
from repro.serve.exec_registry import (
    FilterCache, FilterStats, set_filter_cache,
)


@pytest.fixture(autouse=True)
def filters():
    """A process-wide filter cache of this test's own."""
    cache = FilterCache()
    set_filter_cache(cache)
    yield cache
    set_filter_cache(None)


def _coded(n_sc: int, fft: int, n_rx: int) -> object:
    base = get_scenario("siso-qam16-r12-snr15")
    grid = dataclasses.replace(base.grid, n_subcarriers=n_sc, fft_size=fft,
                               n_rx=n_rx)
    return base.replace(name=f"wc-{n_sc}x{n_rx}", grid=grid)


def _requests(slots) -> list:
    return [SlotRequest(user_id=i, slot=s) for i, s in enumerate(slots)]


def _assert_same_answers(got: dict, ref: dict) -> None:
    a, b = np.asarray(got["cw_llr"]), np.asarray(ref["cw_llr"])
    assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)
    np.testing.assert_array_equal(np.asarray(got["crc_ok"]),
                                  np.asarray(ref["crc_ok"]))


@pytest.mark.parametrize("n_sc,fft,n_rx", [(300, 512, 1), (780, 1024, 4)])
def test_cached_filter_serves_as_the_in_graph_build(n_sc, fft, n_rx):
    scn = _coded(n_sc, fft, n_rx)
    pipe = build_pipeline("classical", scn, fused=True)
    slots = make_traffic(scn, 5, 2)
    ref = pipe.run(stack_slots(slots))  # builds W in its own graph
    runner = BatchRunner(pipe, 2)
    got = runner.run_batch(_requests(slots))
    assert runner.filter_stats.builds == 1
    assert "wiener_w" not in got
    _assert_same_answers(got, ref)


def _small_ladder() -> str:
    try:
        return get_ladder("wc-siso").name
    except KeyError:
        pass
    base = get_scenario("siso-qpsk-r12-snr8")
    grid = dataclasses.replace(base.grid, n_subcarriers=64, fft_size=64,
                               n_taps=4, delay_spread=1.0)
    register_scenario(base.replace(name="wc-qpsk-r12", grid=grid))
    return register_ladder(MCSLadder("wc-siso", ("wc-qpsk-r12",))).name


def test_mesh_lanes_get_their_own_filters():
    ladder = _small_ladder()
    sch = MeshSlotScheduler(
        [closed_cell("lo", ladder, n_users=2, snr_db=6.0),
         closed_cell("hi", ladder, n_users=2, snr_db=12.0)],
        batch_size=2, seed=3,
    )
    built = sch.filter_stats.builds  # the prebuilt template's
    sch.inject_backlog(1)
    [((gi, mcs), lanes)] = sch._plan_tick()
    staged = sch._stage(gi, mcs, lanes)
    nv = np.asarray(staged["noise_var"])
    assert len(lanes) == 2 and nv[0] != nv[1]
    w = np.asarray(staged["wiener_w"])
    for i in range(2):
        np.testing.assert_allclose(
            w[i], np.asarray(classical.wiener_filter(64, 16.0, nv[i])),
            rtol=1e-5, atol=1e-6,
        )
    step = sch._step_for(gi, mcs, sch._bucket(len(lanes)), staged)
    plain = {k: v for k, v in staged.items() if k != "wiener_w"}
    pipe = sch.groups[gi].pipelines[mcs]
    _assert_same_answers(step(staged), jax.jit(jax.vmap(pipe._apply))(plain))
    # one filter per noise variance; the next step's stack only hits
    assert sch.filter_stats.builds - built == 2
    hits = sch.filter_stats.hits
    sch._stage(gi, mcs, lanes)
    assert sch.filter_stats.builds - built == 2
    assert sch.filter_stats.hits - hits == 2


def test_open_loop_mesh_lanes_get_their_own_filters():
    _small_ladder()
    scn = get_scenario("wc-qpsk-r12")
    eng = CellMeshEngine(
        [cell("lo", scn.replace(name="wc-lo", snr_db=6.0)),
         cell("hi", scn.replace(name="wc-hi", snr_db=12.0))],
        batch_size=2,
    )
    eng.submit_traffic(jax.random.PRNGKey(4), 2)
    [group] = eng.groups
    [lanes] = eng._plan(group)
    staged = eng._stage(group, lanes)
    nv = np.asarray(staged["noise_var"])
    assert nv[0] != nv[1]
    w = np.asarray(staged["wiener_w"])
    for i in range(2):
        np.testing.assert_allclose(
            w[i], np.asarray(classical.wiener_filter(64, 16.0, nv[i])),
            rtol=1e-5, atol=1e-6,
        )
    plain = {k: v for k, v in staged.items() if k != "wiener_w"}
    _assert_same_answers(
        eng._group_step(group, staged)(staged),
        jax.jit(jax.vmap(group.pipeline._apply))(plain),
    )


def test_filter_cache_builds_once_per_noise_variance_and_evicts_by_bytes():
    n = 32
    one = n * n * 8  # complex64
    cache = FilterCache(max_bytes=2 * one)
    st = FilterStats()
    a = cache.filter(n, 16.0, np.float32(0.25), stats=st)
    assert cache.filter(n, 16.0, 0.25, stats=st) is a  # same float32 key
    cache.filter(n, 16.0, 0.5, stats=st)
    cache.filter(n, 16.0, 0.25, stats=st)  # 0.5 is now the oldest use
    assert (st.builds, st.hits, st.evictions) == (2, 2, 0)
    cache.filter(n, 16.0, 1.0, stats=st)  # past the bound: 0.5 leaves
    assert (st.builds, st.evictions) == (3, 1)
    assert (len(cache), cache.nbytes) == (2, 2 * one)
    cache.filter(n, 16.0, 0.25, stats=st)
    assert st.builds == 3  # still resident
    cache.filter(n, 16.0, 0.5, stats=st)
    assert (st.builds, st.evictions) == (4, 2)  # built again
    assert cache.stats == st
    d = np.abs(np.subtract.outer(np.arange(n), np.arange(n)))
    r = np.exp(-d / 16.0)
    np.testing.assert_allclose(
        np.asarray(a), np.linalg.solve(r + 0.25 * np.eye(n), r), atol=1e-5)


def test_engine_builds_its_filter_once_while_warming_up():
    scn = _coded(64, 64, 1)
    eng = PhyServeEngine.from_scenario(scn, batch_size=2, fused=True)
    eng.submit_traffic(jax.random.PRNGKey(0), 5)
    first = eng.run()  # warm-up builds; three batches hit
    eng.submit_traffic(jax.random.PRNGKey(1), 5)
    again = eng.run()  # a new runner: warm-up and batches all hit
    assert (first.filter_builds, first.filter_hits) == (1, 3)
    assert (again.filter_builds, again.filter_hits) == (0, 4)
    assert first.filter_evictions == again.filter_evictions == 0
    assert "wiener=1built/3hit/0evicted" in first.summary()


def test_served_step_holds_no_filter_build():
    scn = _coded(780, 1024, 4)
    pipe = build_pipeline("classical", scn, fused=True)
    batch = stack_slots(make_traffic(scn, 7, 2))
    reg = ExecRegistry(persistent=False)
    BatchRunner(pipe, 2, registry=reg).prepare(batch)
    text = {k.receiver: c.as_text() for k, c in reg.items()}
    served, builder = text[pipe.name], text["wiener_filter"]
    inline = jax.jit(pipe._apply).lower(batch).compile().as_text()
    solve = 'jit(solve)'  # op_name of jnp.linalg.solve's operations
    assert solve in builder and solve in inline
    assert solve not in served
    constant = re.compile(r"\[780,780\]\S* constant\(")
    assert not constant.search(served) and not constant.search(builder)


def test_donated_step_keeps_the_cached_filter():
    scn = _coded(64, 64, 1)
    pipe = build_pipeline("classical", scn, fused=True)
    reg = ExecRegistry(persistent=False)
    batch = BatchRunner(pipe, 2, registry=reg).with_filter(
        template_batch(scn, 2))
    step = reg.acquire_pipeline_step(pipe, batch, batch=2, donate=True)
    w = batch["wiener_w"]
    for _ in range(2):
        out = step({k: v if k == "wiener_w" else jnp.array(v)
                    for k, v in batch.items()})
        assert "wiener_w" not in out and not w.is_deleted()
