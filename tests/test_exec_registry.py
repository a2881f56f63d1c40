"""The AOT executable registry (repro.serve.exec_registry).

What must hold for "every compiled step owned in one place" to be safe:

* **key stability** — :class:`ExecKey` for the same pipeline/shape is
  byte-identical across independent processes (pure strings/ints plus a
  deterministic params fingerprint), so the persistent on-disk cache and
  any cross-process tooling can trust key equality.
* **disk round-trip** — a second registry instance on the same cache
  directory rebuilds every executable from disk: ``executables_compiled
  == 0``, ``cache_hits`` == executables needed.  This is the cold-restart
  acceptance criterion in miniature.
* **bucket-policy contract** — every dynamic count 1..max maps onto
  exactly one registered bucket (``bucket_for(n) >= n`` and the image
  over 1..max equals ``buckets(max)``), so precompiling ``buckets(max)``
  guarantees dispatch never JITs.
* **bounded residency** — a capacity-bounded registry evicts LRU-first
  and accounts evictions.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.exec_registry import (
    CostModelBuckets,
    ExecKey,
    ExecRegistry,
    ExecStats,
    FixedBuckets,
    PowerOfTwoBuckets,
    exec_key_for,
    get_registry,
    slot_schema,
    template_batch,
    template_slot,
)

_SCN = "siso-qam16-r12-snr15"


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

_KEY_PROG = (
    "from repro.phy import link; "
    "from repro.phy.scenarios import get_scenario; "
    "from repro.serve.exec_registry import exec_key_for; "
    f"p = link.build_pipeline('classical', get_scenario('{_SCN}')); "
    "print(exec_key_for(p, 4, lanes=2, donate=True, schema='s',"
    " backend='cpu'))"
)


def _key_in_subprocess() -> str:
    env = dict(os.environ)
    import repro

    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-c", _KEY_PROG],
        capture_output=True, text=True, env=env, check=True,
    )
    return out.stdout.strip().splitlines()[-1]


def test_exec_key_stable_across_processes():
    from repro.phy import link
    from repro.phy.scenarios import get_scenario

    p = link.build_pipeline("classical", get_scenario(_SCN))
    here = str(exec_key_for(p, 4, lanes=2, donate=True, schema="s",
                            backend="cpu"))
    assert _key_in_subprocess() == here


def test_exec_key_distinguishes_shape_and_schema():
    from repro.phy import link
    from repro.phy.scenarios import get_scenario

    p = link.build_pipeline("classical", get_scenario(_SCN))
    base = exec_key_for(p, 4)
    assert exec_key_for(p, 8) != base
    assert exec_key_for(p, 4, lanes=2) != base
    assert exec_key_for(p, 4, donate=True) != base
    assert exec_key_for(p, 4, schema="tx_bits+rx_grid") != base
    # one lane bucket on two meshes: two executables
    dev = np.asarray(jax.devices()[:1])
    m11 = jax.sharding.Mesh(dev.reshape(1, 1), ("cell", "batch"))
    m1 = jax.sharding.Mesh(dev.reshape(1), ("cell",))
    assert exec_key_for(p, 4, lanes=2, mesh=m11) \
        != exec_key_for(p, 4, lanes=2, mesh=m1)
    assert exec_key_for(p, 4, lanes=2, mesh=m11) \
        != exec_key_for(p, 4, lanes=2)
    # same everything -> equal and hashable-stable
    assert exec_key_for(p, 4) == base
    assert hash(exec_key_for(p, 4)) == hash(base)


def test_template_schema_matches_runtime_batches():
    from repro.phy.scenarios import get_scenario

    scn = get_scenario(_SCN)
    open_s = slot_schema(template_slot(scn))
    harq_s = slot_schema(template_slot(scn, harq=True))
    assert open_s != harq_s  # HARQ slots carry rv/prior_llr
    batch = template_batch(scn, 3, harq=True)
    assert slot_schema(batch) == harq_s
    assert batch["bits"].shape[0] == 3


# ---------------------------------------------------------------------------
# bucket policies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,max_n", [
    (PowerOfTwoBuckets(), 13),
    (PowerOfTwoBuckets(base=3), 13),
    (FixedBuckets([2, 5, 13]), 13),
    (CostModelBuckets(13), 13),
    (CostModelBuckets(13, compile_cost=0.01), 13),
    (CostModelBuckets(13, compile_cost=1e9), 13),
    (CostModelBuckets(12, quantum=3), 12),
])
def test_bucket_policy_contract(policy, max_n):
    registered = set(policy.buckets(max_n))
    for n in range(1, max_n + 1):
        b = policy.bucket_for(n)
        assert b >= n
        assert b in registered  # precompiling buckets() covers dispatch
    assert registered == {policy.bucket_for(n) for n in range(1, max_n + 1)}


def test_pow2_matches_legacy_mesh_bucketing():
    pol = PowerOfTwoBuckets(base=2)
    # the doubling ladder the mesh planner used to inline
    assert [pol.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [2, 2, 4, 4, 8, 8, 16]


def test_fixed_buckets_reject_over_capacity_and_bad_input():
    pol = FixedBuckets([4, 2, 8])
    assert pol.sizes == (2, 4, 8)
    assert pol.bucket_for(8) == 8
    with pytest.raises(ValueError):
        pol.bucket_for(9)
    with pytest.raises(ValueError):
        pol.bucket_for(0)
    with pytest.raises(ValueError):
        FixedBuckets([])


def test_cost_model_extremes_and_quantum():
    # compile cost ~free -> one bucket per count (no padding at all)
    fine = CostModelBuckets(6, compile_cost=1e-9)
    assert fine.sizes == (1, 2, 3, 4, 5, 6)
    # compile cost enormous -> a single max-size bucket
    coarse = CostModelBuckets(6, compile_cost=1e9)
    assert coarse.sizes == (6,)
    # quantum constrains every bucket to multiples (mesh cell axis)
    q = CostModelBuckets(10, quantum=4, compile_cost=0.1)
    assert all(b % 4 == 0 for b in q.sizes)
    assert q.bucket_for(10) >= 10
    # skewed profile pulls a boundary to the hot count
    skew = CostModelBuckets(
        8, weights=[0, 0, 100, 0, 0, 0, 0, 1], compile_cost=0.5)
    assert 3 in skew.sizes


# ---------------------------------------------------------------------------
# registry residency, stats, persistence
# ---------------------------------------------------------------------------

def _mkkey(i: int, **kw) -> ExecKey:
    kw.setdefault("backend", jax.default_backend())
    return ExecKey(scenario=f"s{i}", receiver="r", precision="fp32",
                   batch=1, lanes=0, **kw)


def test_in_memory_reacquire_is_a_hit():
    reg = ExecRegistry(persistent=False)
    stats = ExecStats()
    fn = lambda x: jnp.tanh(x) @ x.T
    x = jnp.arange(12.0).reshape(3, 4)
    step = reg.acquire(_mkkey(0), fn, x, stats=stats)
    again = reg.acquire(_mkkey(0), fn, x, stats=stats)
    assert again is step
    assert reg.stats.executables_compiled == 1
    assert reg.stats.cache_hits == 1
    assert stats.executables_compiled == 1 and stats.cache_hits == 1
    np.testing.assert_allclose(step(x), np.tanh(x) @ np.asarray(x).T,
                               rtol=1e-6)


def test_capacity_evicts_lru_first():
    reg = ExecRegistry(capacity=2, persistent=False)
    x = jnp.ones((2, 2))
    fns = [lambda v, i=i: v + i for i in range(3)]
    for i in range(3):
        reg.acquire(_mkkey(i), fns[i], x)
    assert len(reg) == 2
    assert reg.evictions == 1
    assert _mkkey(0) not in reg  # least recently acquired went first
    assert _mkkey(1) in reg and _mkkey(2) in reg
    # touching key 1 protects it; key 2 is now LRU
    reg.acquire(_mkkey(1), fns[1], x)
    reg.acquire(_mkkey(0), fns[0], x)
    assert _mkkey(2) not in reg and _mkkey(1) in reg
    rep = reg.report()
    assert rep["resident"] == 2 and rep["evictions"] == 2


def test_disk_cache_round_trip(tmp_path):
    """A second registry instance on the same dir compiles nothing."""
    cache = str(tmp_path / "xla")
    fn = lambda x: jnp.fft.fft(jnp.sin(x) @ x.T).real.sum(-1)
    x = jnp.arange(20.0).reshape(4, 5)
    key = _mkkey(7, schema="roundtrip")

    cold = ExecRegistry(cache_dir=cache)
    out = cold.acquire(key, fn, x)(x)
    assert cold.stats.executables_compiled == 1
    assert cold.stats.cache_hits == 0
    assert cold.stats.compile_time_s > 0

    warm = ExecRegistry(cache_dir=cache)
    assert key not in warm  # fresh in-memory map ...
    out2 = warm.acquire(key, fn, x)(x)
    # ... yet nothing recompiles: the on-disk cache satisfies the build
    assert warm.stats.executables_compiled == 0
    assert warm.stats.cache_hits == 1
    np.testing.assert_allclose(out, out2)


def test_cache_detaches_after_builds(tmp_path):
    """The on-disk cache is scoped to registry builds: after acquire()
    the global cache config is detached, so jits outside the registry
    (donated train steps checkpointed via zero-copy host views) never
    round-trip the serializer."""
    import repro.serve.exec_registry as er

    reg = ExecRegistry(cache_dir=str(tmp_path / "xla"))
    x = jnp.ones((3, 3))
    reg.acquire(_mkkey(3, schema="scoped"), lambda x: (x * 2).sum(0), x)(x)
    assert jax.config.jax_compilation_cache_dir is None
    assert er._ACTIVE_DIR is None
    # an unrelated jit afterwards writes nothing into the registry's dir
    before = sorted((tmp_path / "xla").iterdir())
    jax.jit(lambda x: x @ x + 1.0)(x).block_until_ready()
    assert sorted((tmp_path / "xla").iterdir()) == before


def test_get_registry_follows_env(tmp_path, monkeypatch):
    import repro.serve.exec_registry as er

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla-env"))
    monkeypatch.setattr(er, "_DEFAULT", None)
    reg = get_registry()
    assert reg.cache_dir == str(tmp_path / "xla-env")
    assert get_registry() is reg  # stable while the env holds
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla-env2"))
    assert get_registry() is not reg  # dir change -> fresh registry
    # unset: one fixed directory inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert get_registry().cache_dir == os.path.join(root, ".cache", "jax")
