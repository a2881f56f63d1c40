"""AOT executable registry: every compiled serving step, owned in one place.

TensorPool's sub-millisecond TTI deadlines leave no room for JIT
compilation stalls — the paper's 89% tensor-unit utilization assumes every
kernel is resident *before* the slot fires, the serving-layer analogue of
its L1-residency argument (operands live next to the engines for the whole
computation; executables live next to the dispatcher for the whole serving
run).  Previously each frontend warmed executables ad hoc — per-runner
``warmup()`` calls, per-(group, rung, bucket) ``_warmed`` sets, lazily
built fp32 fallback steps — so first-tick latency spiked and every process
restart recompiled the world.

This module centralizes all of it:

* :class:`ExecKey` — one hashable identity per compiled step: (scenario,
  receiver variant, precision, slot batch, lane bucket, backend, donation,
  slot schema).  Keys are stable across processes (pure strings/ints).
* :class:`ExecRegistry` — an LRU-bounded map ``ExecKey -> Compiled``,
  populated ahead of time via ``jax.jit(...).lower(example).compile()``.
  Lowering happens from *concrete example batches produced by the same
  staging code the dispatch path uses*, so avals, weak types, and mesh
  shardings always match at call time.  Compile time, true XLA compiles,
  and cache hits are accounted both registry-wide and into per-engine
  :class:`ExecStats` accumulators that surface on every serve report.
* **Persistent compilation cache** — the registry wires jax's on-disk XLA
  cache to ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise
  to one fixed directory inside the checkout (``.cache/jax``).  A cold
  process restart then re-serves without recompiling: every ``compile()``
  that the disk cache satisfies counts as a ``cache_hit`` instead of an
  ``executables_compiled``.  Without the variable the cache is attached
  only around the registry's own builds — jits outside the registry never
  round-trip the serializer (see :func:`enable_persistent_cache`).
* :class:`BucketPolicy` — batch-bucketing as an explicit pluggable policy
  (:class:`PowerOfTwoBuckets`, :class:`FixedBuckets`,
  :class:`CostModelBuckets`) instead of logic inlined in the mesh lane
  planner.  A policy maps any dynamic lane count onto one of a small
  registered bucket set, bounding how many step shapes ever compile.
* Template builders (:func:`template_slot`, :func:`template_batch`) —
  deterministic example inputs for ahead-of-time population.  Values are
  irrelevant (XLA's cache keys on the lowered HLO, which depends only on
  avals); structure is everything, so templates ride the exact slot
  builders (:func:`repro.phy.coding.make_coded_slot`,
  :meth:`repro.phy.scenarios.LinkScenario.make_batch`) the runtime uses.
  The closed loop's compiled slot generators
  (:class:`repro.serve.runtime.SlotGenerator`) are registry entries too.

The process-wide default registry (:func:`get_registry`) is shared by
every engine in the process — two schedulers serving the same ladder at
the same batch size share executables instead of recompiling, which is
also why per-engine ``executables_compiled`` is a *history-dependent*
figure (first engine compiles, second one hits).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import pathlib
import time
from typing import Callable, Optional

import jax
import jax.monitoring
import numpy as np
from jax import shard_map
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.serve.trace import span

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/serve/exec_registry.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

__all__ = [
    "BucketPolicy", "CostModelBuckets", "ExecKey", "ExecRegistry",
    "ExecStats", "FixedBuckets", "PowerOfTwoBuckets", "default_cache_dir",
    "disable_persistent_cache",
    "enable_persistent_cache", "exec_key_for", "get_registry",
    "set_registry", "slot_schema", "template_batch", "template_slot",
]


def default_cache_dir() -> str:
    """Where the persistent XLA compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.cache/jax`` in the
    checkout.  The path is part of the cache's key, so it is fixed — never
    a temporary, per-process or per-run name."""
    return os.environ.get(_ENV_VAR) or str(_CHECKOUT / ".cache" / "jax")


# ---------------------------------------------------------------------------
# Persistent-cache wiring + hit/miss counters
# ---------------------------------------------------------------------------
#
# jax's compilation cache emits monitoring events instead of exposing
# counters; one logical compile may touch several cache entries (the
# executable plus auxiliary XLA caches), so attribution is delta-based:
# a compile() whose window saw *zero* misses was satisfied by a cache
# (every true XLA compile reads the persistent cache first and misses).

_EVENTS = {"hits": 0, "misses": 0}
_LISTENING = False
_ACTIVE_DIR: Optional[str] = None
_PREV_DIR: Optional[str] = None  # jax's own setting, restored on detach


def _event_listener(event: str, *a, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _EVENTS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _EVENTS["misses"] += 1


def _ensure_listener() -> None:
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_listener(_event_listener)
        _LISTENING = True


def enable_persistent_cache(path: Optional[str] = None) -> str:
    """Point jax's persistent compilation cache at ``path`` (idempotent).

    Thresholds are zeroed so even fast-compiling mesh steps persist —
    cold-restart time-to-first-slot is the point, not disk frugality.
    Changing the directory mid-process resets the cache singleton so the
    new location takes effect.

    The registry attaches the cache only around its own builds (see
    :meth:`ExecRegistry.acquire`) and :func:`disable_persistent_cache`
    then restores jax's own setting: none, unless
    ``JAX_COMPILATION_CACHE_DIR`` attached the same directory process-wide
    at start-up.  Attaching the checkout cache process-wide would make
    *unrelated* jits round-trip the serializer too, and on the CPU backend
    an executable with donated arguments compiled that way can free
    buffers still referenced by zero-copy host views (observed as a
    segfault when a donated train step runs next to ``np.savez``
    checkpoint snapshots).  Serving compiles all funnel through the
    registry, so scoping loses nothing.
    """
    global _ACTIVE_DIR, _PREV_DIR
    path = path or default_cache_dir()
    if _ACTIVE_DIR == path:
        return path
    _ensure_listener()
    if _ACTIVE_DIR is None:
        _PREV_DIR = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    compilation_cache.reset_cache()
    _ACTIVE_DIR = path
    return path


def disable_persistent_cache() -> None:
    """Detach the registry's cache directory (idempotent).

    Restores the directory jax had before :func:`enable_persistent_cache`
    and resets the cache singleton so a later enable re-attaches cleanly.
    The threshold knobs stay zeroed.
    """
    global _ACTIVE_DIR
    if _ACTIVE_DIR is None:
        return
    jax.config.update("jax_compilation_cache_dir", _PREV_DIR)
    compilation_cache.reset_cache()
    _ACTIVE_DIR = None


# ---------------------------------------------------------------------------
# Keys and stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecKey:
    """Stable identity of one compiled serving step.

    ``lanes == 0`` is a single-cell step (no vmapped lane axis);
    ``lanes > 0`` is a mesh step over that lane bucket, compiled for the
    device mesh named by ``mesh`` (its shape and device ids: the same
    bucket on another mesh is another executable).  ``variant``
    fingerprints the pipeline beyond its display name (stage structure +
    neural-weight digest) so builder options that change the computation
    — ``mmse_smooth``, custom params — never collide.  ``schema`` names
    the slot's batched keys: open-loop and HARQ slots differ in structure
    (``rv`` / ``prior_llr``) and must compile separately.
    """
    scenario: str
    receiver: str
    precision: str
    batch: int
    lanes: int
    backend: str
    variant: str = ""
    donate: bool = False
    schema: str = ""
    mesh: str = ""

    def __str__(self) -> str:
        return "|".join((
            self.scenario, self.receiver, self.precision,
            f"b{self.batch}", f"l{self.lanes}", self.backend,
            self.variant, "donate" if self.donate else "keep", self.schema,
            self.mesh,
        ))


@dataclasses.dataclass
class ExecStats:
    """Per-engine compile accounting (one accumulator per serve frontend).

    ``executables_compiled`` counts true XLA compiles (disk-cache misses);
    ``cache_hits`` counts builds a cache satisfied (the on-disk cache, or
    jax's in-process cache) plus in-memory registry re-acquires;
    ``compile_time_s`` is wall time spent
    inside ``lower().compile()`` either way.  With a warm on-disk cache a
    fresh process therefore reaches its first served slot with
    ``executables_compiled == 0`` and ``cache_hits`` == executables needed.
    """
    compile_time_s: float = 0.0
    executables_compiled: int = 0
    cache_hits: int = 0

    def add(self, compile_s: float, compiled: bool, hit: bool) -> None:
        self.compile_time_s += compile_s
        self.executables_compiled += int(compiled)
        self.cache_hits += int(hit)

    def merge(self, other: "ExecStats") -> "ExecStats":
        self.compile_time_s += other.compile_time_s
        self.executables_compiled += other.executables_compiled
        self.cache_hits += other.cache_hits
        return self

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def slot_schema(slot: dict) -> str:
    """Compact structural tag of a slot batch: its batched keys.

    Side-info keys are scenario-determined (the scenario is already in
    the key); the batched keys are what distinguish open-loop slots from
    HARQ slots carrying ``rv`` + ``prior_llr``.
    """
    from repro.serve.runtime import BATCHED_KEYS

    return "+".join(k for k in BATCHED_KEYS if k in slot)


def _pipeline_variant(pipeline) -> str:
    """Stage-structure + params fingerprint (cached on the pipeline)."""
    v = getattr(pipeline, "_exec_variant", None)
    if v is None:
        parts = [st.name for st in pipeline.stages]
        if pipeline.params is not None:
            h = hashlib.blake2b(digest_size=8)
            for leaf in jax.tree_util.tree_leaves(pipeline.params):
                a = np.asarray(leaf)
                h.update(str(a.shape).encode())
                h.update(str(a.dtype).encode())
                h.update(a.tobytes())
            parts.append(h.hexdigest())
        v = hashlib.blake2b(
            "/".join(parts).encode(), digest_size=8
        ).hexdigest()
        try:
            pipeline._exec_variant = v
        except Exception:
            pass
    return v


def exec_key_for(pipeline, batch: int, *, lanes: int = 0,
                 donate: bool = False, schema: str = "",
                 backend: Optional[str] = None, mesh=None) -> ExecKey:
    """The :class:`ExecKey` of ``pipeline``'s step at (batch, lanes)."""
    mesh_tag = "" if mesh is None else "x".join(
        str(d) for d in mesh.devices.shape
    ) + "@" + ",".join(str(d.id) for d in mesh.devices.flat)
    return ExecKey(
        scenario=pipeline.scenario.name,
        receiver=pipeline.name,
        precision=pipeline.precision,
        batch=int(batch),
        lanes=int(lanes),
        backend=backend or jax.default_backend(),
        variant=_pipeline_variant(pipeline),
        donate=bool(donate),
        schema=schema,
        mesh=mesh_tag,
    )


def _example_mesh(example: dict):
    """The one device mesh a staged example is sharded over, or None."""
    meshes = {
        v.sharding.mesh for v in example.values()
        if isinstance(getattr(v, "sharding", None), NamedSharding)
    }
    if len(meshes) > 1:
        raise ValueError(f"example spans {len(meshes)} device meshes")
    return meshes.pop() if meshes else None


def mesh_step(fn: Callable, example: dict, mesh) -> Callable:
    """``fn`` (one lane-vmapped step) as a ``shard_map`` over ``mesh``.

    Every lane and every slot of a lane is independent, so each device
    runs the step on its own (cell, batch) block, with no collective.
    This is required, not an optimization: the partitioner cannot split
    a Mosaic kernel, so a step holding one does not compile on a
    multi-device mesh unless it is partitioned by hand.  Input specs are
    the example's shardings; outputs that pass an input through keep its
    spec, and every other output is (lane, slot)-batched like the slot's
    received grid ``y``.
    """
    in_specs = {k: v.sharding.spec for k, v in example.items()}
    batched = P(*tuple(in_specs["y"])[:2])
    out_specs = {
        k: in_specs.get(k, batched)
        for k in jax.eval_shape(fn, example)
    }
    # check_vma=False: pallas_call output shapes carry no per-axis
    # variance annotation, and nothing here is replicated to check
    return shard_map(fn, mesh=mesh, in_specs=(in_specs,),
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Templates: deterministic example inputs for ahead-of-time population
# ---------------------------------------------------------------------------

def template_slot(scenario, *, harq: bool = False) -> dict:
    """One batch-1 example slot of ``scenario`` (fixed key; values are
    irrelevant to compilation — only avals reach the lowered HLO).

    ``harq=True`` builds the closed-loop schema: a coded slot at RV 0
    with the zeroed combining-LLR prior riding along — the keys, shapes
    and dtypes :meth:`repro.serve.runtime.CellLoop.make_slot` stages
    from its compiled :class:`~repro.serve.runtime.SlotGenerator`.
    """
    key = jax.random.PRNGKey(0)
    if not harq:
        return scenario.make_batch(key, 1)
    from repro.phy import coding

    assert scenario.code is not None, (
        f"{scenario.name}: HARQ templates need a coded scenario"
    )
    slot = coding.make_coded_slot(key, scenario, 1, rv=0)
    slot["prior_llr"] = np.zeros(
        (1, coding.codewords_per_slot(scenario), scenario.code.n_mother),
        np.float32,
    )
    return slot


def template_batch(scenario, batch: int, *, harq: bool = False) -> dict:
    """A stacked ``batch``-slot example, through the runtime's own
    :func:`~repro.serve.runtime.stack_slots` so padding/stacking avals
    match dispatch exactly."""
    from repro.serve.runtime import stack_slots

    return stack_slots([template_slot(scenario, harq=harq)], batch - 1)


# ---------------------------------------------------------------------------
# Batch-bucketing policies
# ---------------------------------------------------------------------------

class BucketPolicy:
    """Maps a dynamic lane/batch count onto one registered static bucket.

    The contract every policy keeps: ``bucket_for(n) >= n`` for every n it
    accepts, and the image of ``bucket_for`` over ``1..max_n`` is exactly
    ``buckets(max_n)`` — so an engine that precompiles ``buckets(max_n)``
    never JITs at dispatch time.
    """

    def bucket_for(self, n: int) -> int:
        raise NotImplementedError

    def buckets(self, max_n: int) -> tuple:
        """Every bucket 1..max_n maps onto (the precompile set)."""
        return tuple(sorted({
            self.bucket_for(n) for n in range(1, max(int(max_n), 1) + 1)
        }))


class PowerOfTwoBuckets(BucketPolicy):
    """Doubling buckets from ``base`` — at most log2 step shapes.

    With ``base`` = the mesh's cell-axis size this reproduces the lane
    bucketing previously inlined in the mesh planner, so default
    trajectories are unchanged.
    """

    def __init__(self, base: int = 1):
        self.base = max(int(base), 1)

    def bucket_for(self, n: int) -> int:
        if n < 1:
            raise ValueError(f"lane count must be >= 1, got {n}")
        b = self.base
        while b < n:
            b *= 2
        return b

    def __repr__(self) -> str:
        return f"PowerOfTwoBuckets(base={self.base})"


class FixedBuckets(BucketPolicy):
    """An explicit ascending bucket set; counts above the top are an
    error (the operator declared the capacity envelope)."""

    def __init__(self, sizes):
        self.sizes = tuple(sorted({int(s) for s in sizes}))
        if not self.sizes or self.sizes[0] < 1:
            raise ValueError(f"invalid bucket sizes {sizes!r}")

    def bucket_for(self, n: int) -> int:
        if n < 1:
            raise ValueError(f"lane count must be >= 1, got {n}")
        for s in self.sizes:
            if s >= n:
                return s
        raise ValueError(
            f"lane count {n} exceeds the largest bucket {self.sizes[-1]} "
            f"of {self!r}"
        )

    def __repr__(self) -> str:
        return f"{type(self).__name__}(sizes={self.sizes})"


class CostModelBuckets(FixedBuckets):
    """Bucket set chosen by a padded-cost model over a lane-count profile.

    Dynamic-programming partition of ``1..max_n``: each bucket ``b``
    serves every count in its span at cost ``b`` lanes (padding included),
    weighted by ``weights[n-1]`` (expected frequency of count ``n``,
    uniform by default), plus ``compile_cost`` per registered bucket (the
    compile-time/registry-capacity price of one more step shape).  Small
    ``compile_cost`` approaches one bucket per count; large approaches a
    single max-size bucket.  ``quantum`` constrains buckets to multiples
    (mesh cell-axis divisibility).
    """

    def __init__(self, max_n: int, *, weights=None,
                 compile_cost: float = 4.0, quantum: int = 1):
        max_n = int(max_n)
        quantum = max(int(quantum), 1)
        if max_n < 1:
            raise ValueError(f"max_n must be >= 1, got {max_n}")
        if weights is None:
            weights = [1.0] * max_n
        weights = [float(w) for w in weights]
        if len(weights) != max_n:
            raise ValueError(
                f"weights has {len(weights)} entries for max_n={max_n}"
            )
        # candidate bucket boundaries: multiples of the quantum
        cands = [b for b in range(quantum, max_n + quantum, quantum)]
        # prefix[i] = total weight of counts 1..i
        prefix = [0.0] * (max_n + 1)
        for n in range(1, max_n + 1):
            prefix[n] = prefix[n - 1] + weights[n - 1]
        # best[i] = (cost, chosen buckets) covering counts 1..cands[i]
        best: list = []
        for i, b in enumerate(cands):
            lo_w = lambda j: prefix[min(b, max_n)] - prefix[
                min(cands[j], max_n)]
            # bucket b alone covers 1..b
            cost = compile_cost + b * prefix[min(b, max_n)]
            choice = (cost, (b,))
            for j in range(i):
                span_w = (prefix[min(b, max_n)]
                          - prefix[min(cands[j], max_n)])
                c = best[j][0] + compile_cost + b * span_w
                if c < choice[0]:
                    choice = (c, best[j][1] + (b,))
            best.append(choice)
        super().__init__(best[-1][1])
        self.max_n = max_n
        self.quantum = quantum


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Entry:
    compiled: object  # jax Compiled
    compile_s: float
    from_disk: bool


class ExecRegistry:
    """LRU-bounded map of :class:`ExecKey` -> AOT-compiled executable.

    ``capacity`` bounds resident executables (None = unbounded);
    least-recently-acquired entries evict first.  ``persistent=True``
    (default) wires the on-disk XLA cache before every compile, so an
    evicted or cold-restarted executable rebuilds from disk instead of
    recompiling.
    """

    def __init__(self, *, capacity: Optional[int] = None,
                 cache_dir: Optional[str] = None, persistent: bool = True):
        self.capacity = capacity
        self.persistent = persistent
        self.cache_dir = (cache_dir or default_cache_dir()) \
            if persistent else None
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.stats = ExecStats()  # registry-wide accounting
        self.lookups = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: ExecKey) -> bool:
        return key in self._entries

    def keys(self) -> list:
        return list(self._entries)

    def items(self) -> list:
        """(key, compiled executable) for every resident entry."""
        return [(k, e.compiled) for k, e in self._entries.items()]

    # -- acquisition ------------------------------------------------------
    def acquire(self, key: ExecKey, fn: Callable, example,
                *, stats: Optional[ExecStats] = None):
        """The compiled executable for ``key``, building it if absent.

        ``fn`` is the step function (arg 0 = the slot batch) and
        ``example`` a concrete input produced by the dispatch path's own
        staging code — lowering from it bakes the exact avals, weak
        types, and shardings dispatch will use.  Compilation happens
        here, ahead of the timed serving window; execution never does.
        """
        self.lookups += 1
        ent = self._entries.get(key)
        if ent is not None:
            self._entries.move_to_end(key)
            self.stats.add(0.0, False, True)
            if stats is not None:
                stats.add(0.0, False, True)
            return ent.compiled

        jit_kw = {"donate_argnums": 0} if key.donate else {}
        h0, m0 = _EVENTS["hits"], _EVENTS["misses"]
        t0 = time.perf_counter()
        with span("serve.acquire") as sp:
            # the on-disk cache is attached only for the registry's own
            # build window: process-wide attachment drags unrelated jits
            # (donated train steps) through the serializer, which
            # corrupts buffer lifetimes on CPU — see
            # enable_persistent_cache's docstring
            if self.persistent:
                enable_persistent_cache(self.cache_dir)
            try:
                compiled = jax.jit(fn, **jit_kw).lower(example).compile()
            finally:
                if self.persistent:
                    disable_persistent_cache()
            dt = time.perf_counter() - t0
            del h0  # hit events corroborate but don't decide attribution
            misses = _EVENTS["misses"] - m0
            # a true XLA compile always reads the persistent cache first
            # and misses; zero misses therefore means *some* cache
            # satisfied the build (the on-disk cache, or jax's in-process
            # executable cache when this computation already compiled
            # this process)
            from_cache = self.persistent and misses == 0
            sp.set_metadata(compiled=int(not from_cache),
                            cache_hit=int(from_cache))
        self.stats.add(dt, not from_cache, from_cache)
        if stats is not None:
            stats.add(dt, not from_cache, from_cache)
        self._entries[key] = _Entry(compiled, dt, from_cache)
        while (self.capacity is not None
               and len(self._entries) > self.capacity):
            self._entries.popitem(last=False)
            self.evictions += 1
        return compiled

    def acquire_pipeline_step(self, pipeline, example, *, batch: int,
                              lanes: int = 0, donate: bool = False,
                              stats: Optional[ExecStats] = None):
        """Acquire ``pipeline``'s serving step over ``example``.

        ``lanes == 0`` compiles the single-cell step (``pipeline._apply``
        over a stacked batch); ``lanes > 0`` the mesh step
        (``vmap(pipeline._apply)`` over staged (lanes, batch, ...)
        arrays, shard-mapped over their mesh by :func:`mesh_step`).
        """
        mesh = _example_mesh(example) if lanes else None
        key = exec_key_for(
            pipeline, batch, lanes=lanes, donate=donate,
            schema=slot_schema(example), mesh=mesh,
        )
        fn = pipeline._apply
        if lanes:
            fn = jax.vmap(fn)
            if mesh is not None:
                fn = mesh_step(fn, example, mesh)
        return self.acquire(key, fn, example, stats=stats)

    # -- reporting --------------------------------------------------------
    def report(self) -> dict:
        return {
            "resident": len(self._entries),
            "lookups": self.lookups,
            "evictions": self.evictions,
            "cache_dir": self.cache_dir,
            **self.stats.as_dict(),
        }


_DEFAULT: Optional[ExecRegistry] = None


def get_registry() -> ExecRegistry:
    """The process-wide default registry (shared across every engine).

    Re-created when the env-resolved cache dir changes — tests that point
    ``JAX_COMPILATION_CACHE_DIR`` at a tmp dir get a fresh registry on
    that dir.
    """
    global _DEFAULT
    if _DEFAULT is None or _DEFAULT.cache_dir != default_cache_dir():
        _DEFAULT = ExecRegistry()
    return _DEFAULT


def set_registry(reg: Optional[ExecRegistry]) -> None:
    """Install (or with ``None`` drop) the process-wide registry."""
    global _DEFAULT
    _DEFAULT = reg
