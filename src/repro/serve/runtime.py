"""Closed-loop TTI serving runtime + the shared slot-scheduler core.

Real base stations are closed-loop: every transport block is ACK/NACKed,
failed blocks come back as HARQ retransmissions whose soft bits combine
with the buffered LLRs of earlier rounds, and the MCS adapts to the
observed BLER.  This module is the serving layer's shared core plus that
closed loop:

* **Shared core** — :class:`SlotRequest` / :class:`PhyServeReport`,
  submit bookkeeping (:class:`SlotLedger`), batch stacking/padding
  (:func:`stack_slots`), traffic generation (:func:`make_traffic`, with
  single-seed reproducibility via :func:`cell_rng`), slot-metric
  aggregation (:func:`slot_metric_means`) and report construction
  (:func:`build_serve_report`), and the timed batch executor
  (:class:`BatchRunner`).  The open-loop frontends
  (:class:`repro.serve.phy_engine.PhyServeEngine`,
  :class:`repro.serve.cell_mesh.CellMeshEngine`) are thin layers over
  these pieces, so single-cell, multi-cell, and closed-loop serving all
  batch, time, and score slots identically.

* **Closed loop** — the per-cell state machine lives in
  :class:`CellLoop`: Poisson arrivals into per-user queues, one slot per
  user per TTI grouped by (MCS, SNR) into fixed-size batches (the MCS
  picks the rung's single compiled executable, and the SNR must be
  batch-uniform because ``noise_var`` is scalar side info — the same
  constraint as a mesh lane), CRC ACK/NACK feedback, HARQ
  retransmissions at the next redundancy version with combined channel
  LLRs riding along as the decode prior (chase + incremental redundancy,
  :mod:`repro.phy.coding`), and OLLA-style link adaptation over an
  :class:`repro.phy.scenarios.MCSLadder`.  :class:`SlotScheduler` drives
  one CellLoop through per-rung :class:`BatchRunner` executables;
  :class:`repro.serve.cell_mesh.MeshSlotScheduler` drives hundreds of
  CellLoops in TTI lockstep over a ``(cell, batch)`` device mesh —
  because both frontends share the state machine, a 1-cell mesh run and
  a single-cell run produce identical closed-loop trajectories.

HARQ buffer lifecycle (the serving-level analogue of the paper's L1
data-reuse argument): a process's combined-LLR buffer is *created* on the
first NACK, *accumulated into* by every retransmission's de-rate-matched
window, and *freed* on delivery or max-retx exhaustion — soft state lives
exactly as long as the block is in flight, like TensorPool keeps decoder
state L1-resident across min-sum iterations instead of round-tripping it.

Every transport-block job carries a unique ``job_id`` and ends in exactly
one of four states — delivered, exhausted, shed, or still queued — with
the finalized ids recorded per cell (:attr:`CellLoop.finalized_jobs`), so
the invariant tests can assert conservation (no loss, no duplication)
even across inter-cell handover.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.phy import link as _link
from repro.serve.exec_registry import (
    ExecKey, ExecStats, FilterStats, get_filter_cache, get_registry,
    slot_schema, template_batch,
)
from repro.serve.trace import shape_counts, span, step_window, wait

# slot keys with a leading per-user batch axis; everything else is
# scenario-static side info shared by every user.  "info_bits" only
# exists on coded slots; "rv" / "prior_llr" only on HARQ-aware slots
# from the closed-loop scheduler — stacking skips absent keys.
BATCHED_KEYS = ("y_time", "y", "x", "h", "bits", "info_bits", "rv",
                "prior_llr")

# the slot-mean metrics every serving report aggregates (BER / CHE-MSE on
# all links, BLER / decode effort on coded links)
METRIC_KEYS = ("ber", "che_mse", "bler", "decode_iters")

TTI_S = 1e-3  # the paper's slot deadline


@dataclasses.dataclass
class SlotRequest:
    """One user's uplink slot awaiting processing."""
    user_id: int
    slot: dict  # link-slot dict with batch dim 1 on BATCHED_KEYS
    metrics: Optional[dict] = None
    done: bool = False


@dataclasses.dataclass
class PhyServeReport:
    pipeline: str
    scenario: str
    n_slots: int
    n_batches: int
    batch_size: int
    wall_s: float
    slots_per_sec: float
    ber: Optional[float]
    che_mse: Optional[float]
    tti: dict  # pipeline.tti_report(batch=batch_size); may be empty
    stage_cycles: dict  # per-stage BlockCycles; may be empty
    # coded-link metrics (None on uncoded scenarios)
    bler: Optional[float] = None
    info_bits_per_sec: Optional[float] = None
    decode_iters: Optional[float] = None
    # modeled energy at the pipeline's precision policy (costmodel):
    # per-slot joules over the TensorPool cycle budget, the resulting
    # efficiency, and how much operand traffic stayed in L1
    precision: str = "fp32"
    energy_uj_per_slot: Optional[float] = None
    gops_per_watt: Optional[float] = None
    l1_residency: Optional[float] = None
    # AOT executable accounting (exec_registry): wall time spent compiling
    # for this engine, true XLA compiles vs persistent/registry cache hits,
    # and first vs steady-state batch latency — compile cost is part of
    # the perf trajectory, not hidden warmup
    compile_time_s: float = 0.0
    executables_compiled: int = 0
    cache_hits: int = 0
    first_tick_s: Optional[float] = None
    steady_tick_s: Optional[float] = None
    # Wiener filter cache (exec_registry.FilterCache): filters this run
    # built, lookups a resident filter served, filters its builds evicted
    filter_builds: int = 0
    filter_hits: int = 0
    filter_evictions: int = 0

    def summary(self) -> str:
        parts = [
            f"{self.pipeline}: {self.n_slots} slots in {self.wall_s:.3f}s "
            f"({self.slots_per_sec:.1f} slots/s, batch={self.batch_size})"
        ]
        if self.ber is not None:
            parts.append(f"BER={self.ber:.4f}")
        if self.bler is not None:
            parts.append(f"BLER={self.bler:.4f}")
        if self.info_bits_per_sec is not None:
            parts.append(
                f"goodput={self.info_bits_per_sec/1e6:.2f} Mbit/s"
            )
        if self.decode_iters is not None:
            parts.append(f"dec-iters={self.decode_iters:.1f}")
        if self.che_mse is not None:
            parts.append(f"CHE-MSE={self.che_mse:.4f}")
        # pipelines without cycle estimators report no TTI budget
        util = self.tti.get("tti_utilization") if self.tti else None
        if util is not None:
            parts.append(
                f"TTI util={util:.3f} (fits={self.tti.get('fits_tti')})"
            )
        if self.gops_per_watt is not None:
            parts.append(
                f"{self.precision}: {self.gops_per_watt:.0f} GOPS/W "
                f"(L1 res={self.l1_residency:.2f})"
            )
        if self.executables_compiled or self.cache_hits:
            parts.append(
                f"compile={self.compile_time_s:.2f}s "
                f"({self.executables_compiled}x/{self.cache_hits}hit)"
            )
        if self.filter_builds or self.filter_hits:
            parts.append(
                f"wiener={self.filter_builds}built/{self.filter_hits}hit"
                f"/{self.filter_evictions}evicted"
            )
        return "  ".join(parts)


class SlotLedger:
    """Monotone user-id allocation + request construction — the submit
    bookkeeping previously duplicated by both serve engines."""

    def __init__(self):
        self._next_uid = 0

    def new_request(self, slot: dict,
                    user_id: Optional[int] = None) -> SlotRequest:
        if user_id is None:
            user_id = self._next_uid
        self._next_uid = max(self._next_uid, user_id) + 1
        return SlotRequest(user_id=user_id, slot=slot)


def validate_slots(slots: list, keys=BATCHED_KEYS) -> None:
    """Check a batch's slots agree on keys, trailing shapes, and dtypes.

    Mismatched slots used to surface as opaque XLA shape errors from
    inside ``jit`` (or worse, silent mis-stacking); this names the
    offending key and slot up front.  Batched keys may differ in their
    leading (batch) dimension only; everything after it is the
    scenario's static structure and must match the head slot exactly.
    """
    head = slots[0]
    for i, s in enumerate(slots[1:], 1):
        extra, missing = set(s) - set(head), set(head) - set(s)
        if extra or missing:
            raise ValueError(
                f"slot {i} keys differ from slot 0: "
                f"missing {sorted(missing)}, unexpected {sorted(extra)} "
                "— all slots in a batch must come from the same scenario/"
                "slot builder"
            )
        for k in keys:
            if k not in head:
                continue
            a, b = np.shape(head[k]), np.shape(s[k])
            if a[1:] != b[1:]:
                raise ValueError(
                    f"slot {i} key {k!r}: shape {b} != {a} of slot 0 "
                    "(trailing dims are scenario-static and must match; "
                    "check grid/code/MCS consistency of the batch)"
                )
            da = getattr(head[k], "dtype", None)
            db = getattr(s[k], "dtype", None)
            if da != db:
                raise ValueError(
                    f"slot {i} key {k!r}: dtype {db} != {da} of slot 0"
                )


def stack_slots(slots: list, pad: int = 0, keys=BATCHED_KEYS, xp=jnp
                ) -> dict:
    """Stack per-user slots (batch dim 1 each) into one batched slot.

    ``pad`` repeats ``slots[0]`` to reach a static batch size; non-batched
    side info is taken from the first slot (it is scenario-static).
    ``xp`` picks the array backend: jnp for direct device dispatch, np for
    host-side staging (the mesh engine stacks lanes before transfer).
    Slots are validated first (:func:`validate_slots`) so shape/dtype
    mismatches fail with the offending key named instead of an XLA error.
    """
    validate_slots(slots, keys)
    slots = list(slots) + [slots[0]] * pad
    batch = dict(slots[0])
    for k in keys:
        if k in batch:
            batch[k] = xp.concatenate(
                [xp.asarray(s[k]) for s in slots], axis=0
            )
    return batch


def cell_rng(seed: int, cell: int = 0) -> np.random.Generator:
    """One deterministic Generator per (seed, cell index).

    Every source of serving randomness — Poisson arrivals, per-user SNR
    spread, and the jax keys behind slot/channel/noise realizations
    (:func:`rng_key`) — draws from this single stream, so any engine
    (single cell, mesh, closed loop) is reproducible from one ``seed=``,
    and cell ``i`` of a mesh run replays identically as a standalone
    single-cell run seeded with the same ``(seed, i)``.
    """
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(cell)])
    )


def rng_seed(rng: np.random.Generator) -> int:
    """Draw the integer seed of a fresh jax PRNG key from a numpy
    Generator stream."""
    return int(rng.integers(0, 2**31 - 1))


def rng_key(rng: np.random.Generator) -> jax.Array:
    """Draw a fresh jax PRNG key from a numpy Generator stream."""
    return jax.random.PRNGKey(rng_seed(rng))


def make_traffic(scenario, rng, n: int) -> list:
    """Simulate ``n`` independent single-slot arrivals of ``scenario``.

    ``rng`` is a jax PRNG key (split ``n`` ways), an int seed, or a
    :class:`numpy.random.Generator` — the latter two route through
    :func:`cell_rng`/:func:`rng_key` so every engine draws traffic from
    one reproducible per-seed stream instead of per-call key plumbing.
    """
    if isinstance(rng, (int, np.integer)):
        rng = cell_rng(int(rng))
    if isinstance(rng, np.random.Generator):
        keys = [rng_key(rng) for _ in range(n)]
    else:
        keys = jax.random.split(rng, n)
    return [scenario.make_batch(k, 1) for k in keys]


def slot_metric_means(metric_dicts) -> dict:
    """Slot-weighted means of the standard per-slot metrics.

    One aggregation for every serving report (single-cell engine, mesh
    per-cell reports, closed-loop scheduler): each metric averages over
    the slots that carry it, absent metrics aggregate to None.
    """
    out = {}
    vals = {k: [] for k in METRIC_KEYS}
    for m in metric_dicts:
        if not m:
            continue
        for k in METRIC_KEYS:
            if k in m:
                vals[k].append(m[k])
    for k, v in vals.items():
        out[k] = float(np.mean(v)) if v else None
    return out


def first_steady(times) -> tuple:
    """``(first, steady)`` latency split of a duration series: the first
    entry (cold path: any residual dispatch/transfer setup) vs the median
    of the rest (the steady state the throughput claim is about)."""
    times = [float(t) for t in times]
    if not times:
        return None, None
    first = times[0]
    steady = float(np.median(times[1:])) if len(times) > 1 else first
    return first, steady


def build_serve_report(pipeline: _link.ReceiverPipeline, scenario,
                       metric_dicts, *, n_slots: int, n_batches: int,
                       batch_size: int, wall_s: float,
                       exec_stats=None, batch_times=(),
                       filter_stats=None) -> PhyServeReport:
    """Aggregate served-slot metrics into a :class:`PhyServeReport` —
    shared by the single-cell engine and the mesh's per-cell reports so
    the two always agree (incl. the goodput definition)."""
    means = slot_metric_means(metric_dicts)
    wall_safe = max(wall_s, 1e-9)
    goodput = None
    if means["bler"] is not None and scenario.code is not None:
        from repro.phy import coding

        goodput = coding.goodput_bits(
            scenario, means["bler"], n_slots
        ) / wall_safe
    # modeled per-slot energy at the pipeline's precision (skipped for
    # pipelines whose stages carry no cycle estimators)
    energy = gops_w = l1_res = None
    if pipeline.stage_cycles():
        er = pipeline.energy_report()
        energy = er.total_j * 1e6
        gops_w = er.gops_per_watt
        l1_res = er.l1_residency
    first_s, steady_s = first_steady(batch_times)
    return PhyServeReport(
        pipeline=pipeline.name,
        scenario=scenario.name,
        n_slots=n_slots,
        n_batches=n_batches,
        batch_size=batch_size,
        wall_s=wall_s,
        slots_per_sec=n_slots / wall_safe,
        ber=means["ber"],
        che_mse=means["che_mse"],
        tti=pipeline.tti_report(batch=batch_size),
        stage_cycles=pipeline.stage_cycles(),
        bler=means["bler"],
        info_bits_per_sec=goodput,
        decode_iters=means["decode_iters"],
        precision=pipeline.precision,
        energy_uj_per_slot=energy,
        gops_per_watt=gops_w,
        l1_residency=l1_res,
        compile_time_s=exec_stats.compile_time_s if exec_stats else 0.0,
        executables_compiled=(
            exec_stats.executables_compiled if exec_stats else 0
        ),
        cache_hits=exec_stats.cache_hits if exec_stats else 0,
        first_tick_s=first_s,
        steady_tick_s=steady_s,
        filter_builds=filter_stats.builds if filter_stats else 0,
        filter_hits=filter_stats.hits if filter_stats else 0,
        filter_evictions=filter_stats.evictions if filter_stats else 0,
    )


class BatchRunner:
    """One pipeline + timed fixed-shape batch execution.

    The execution core under every serving path: stacks up to
    ``batch_size`` requests (padding by repetition so each slot structure
    compiles exactly once), runs the AOT-compiled step from the process's
    :class:`~repro.serve.exec_registry.ExecRegistry` with the timed window
    covering only the executable, and records per-request metrics.

    ``warmup()``/:meth:`prepare` *acquire* the executable (compiling it —
    or loading it from the persistent cache — outside the timed window)
    without executing anything, so warming no longer double-serves the
    first chunk and is a no-op once the executable is resident.  Compile
    accounting lands in ``exec_stats``; per-batch latencies in
    ``batch_times`` (first vs steady state on the report).

    A pipeline with a Wiener stage gets its filter as the batch's
    ``wiener_w``, from the process-wide
    :class:`~repro.serve.exec_registry.FilterCache` (lookups in
    ``filter_stats``); :meth:`prepare` builds it before the window.
    """

    def __init__(self, pipeline: _link.ReceiverPipeline, batch_size: int,
                 *, registry=None):
        self.pipeline = pipeline
        self.batch_size = batch_size
        self.registry = registry if registry is not None else get_registry()
        self.exec_stats = ExecStats()
        self.wall_s = 0.0
        self.n_batches = 0
        self.batch_times: list[float] = []
        self._execs: dict = {}  # slot schema -> AOT-compiled step
        self.span_counts = shape_counts(pipeline.scenario)
        self.filter_stats = FilterStats()

    def with_filter(self, batch: dict, noise_var=None) -> dict:
        """``batch`` with the Wiener filter its step reads as
        ``wiener_w``, looked up in the filter cache; ``batch`` itself when
        the pipeline has no Wiener stage or the filter is there already.

        ``noise_var`` is the batch's noise variance as the host holds it;
        None takes the batch's own ``noise_var`` side info (a host scalar
        on slots made there, as the benchmark's pools are; a device array
        is read to the host)."""
        corr_len = self.pipeline.wiener_corr_len
        if corr_len is None or "wiener_w" in batch:
            return batch
        w = get_filter_cache().filter(
            self.pipeline.scenario.grid.n_subcarriers, corr_len,
            batch["noise_var"] if noise_var is None else noise_var,
            stats=self.filter_stats, registry=self.registry,
        )
        return {**batch, "wiener_w": w}

    def prepare(self, batch: dict):
        """Acquire the AOT step for ``batch``'s slot structure and the
        Wiener filter of its noise variance (no execution).  Idempotent
        per schema; the registry satisfies repeat acquisitions in memory
        and cold ones from the persistent cache."""
        batch = self.with_filter(batch)
        schema = slot_schema(batch)
        step = self._execs.get(schema)
        if step is None:
            step = self.registry.acquire_pipeline_step(
                self.pipeline, batch, batch=self.batch_size,
                stats=self.exec_stats,
            )
            self._execs[schema] = step
        return step

    def warmup(self, reqs: list) -> None:
        self.prepare(stack_slots(
            [r.slot for r in reqs], self.batch_size - len(reqs)
        ))

    def _step(self, batch: dict) -> dict:
        """Run ``batch`` (with its filter, :meth:`with_filter`) through
        the resident executable (acquiring it first if a caller skipped
        :meth:`prepare`)."""
        return self.prepare(batch)(batch)

    def _execute(self, batch: dict) -> dict:
        """Run one stacked batch inside the timed window.  Overridable:
        :class:`repro.serve.supervisor.SupervisedBatchRunner` interposes
        retry and non-finite-guard handling here."""
        with step_window(self, **self.span_counts) as window:
            state = wait(self._step(batch))
        self.batch_times.append(window["dt"])
        return state

    def run_batch(self, reqs: list, noise_var=None) -> dict:
        """Serve one chunk of requests; returns the raw pipeline state.

        Marks each request done with its per-slot metrics; padded tail
        results are discarded.  ``noise_var``: see :meth:`with_filter`.
        """
        with span("serve.batch", slots=len(reqs), **self.span_counts):
            with span("serve.stack"):
                batch = stack_slots(
                    [r.slot for r in reqs], self.batch_size - len(reqs)
                )
            batch = self.with_filter(batch, noise_var)
            state = self._execute(batch)
            self.n_batches += 1
            with span("serve.slot_metrics"):
                metrics = _link.slot_metrics(
                    state, self.pipeline.scenario, per_slot=True
                )
                metrics = {k: np.asarray(v) for k, v in metrics.items()}
            for j, r in enumerate(reqs):
                r.metrics = {k: float(v[j]) for k, v in metrics.items()}
                r.done = True
        return state

    def drain(self, reqs: list, warmup: bool = True) -> int:
        """Serve ``reqs`` in fixed-size chunks; returns the chunk count."""
        chunks = [
            reqs[i : i + self.batch_size]
            for i in range(0, len(reqs), self.batch_size)
        ]
        if warmup and chunks:
            self.warmup(chunks[0])
        for chunk in chunks:
            self.run_batch(chunk)
        return len(chunks)


# ---------------------------------------------------------------------------
# Closed-loop TTI scheduling: the per-cell state machine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HarqProcess:
    """Soft state of one in-flight slot's transport blocks.

    ``prior`` is the combined channel-LLR buffer (C, n_mother) —
    allocated on the first NACK, accumulated by every retransmission,
    freed on delivery or exhaustion.  ``acked`` marks blocks that already
    passed CRC in an earlier round (they ride along in retransmitted
    slots but their feedback is final).
    """
    mcs: int
    info: np.ndarray  # (1, C, k_info) transport-block payloads
    prior: np.ndarray  # (1, C, n_mother) combined channel LLRs
    acked: np.ndarray  # (C,) bool
    n_tx: int = 0  # transmissions completed so far
    rv: int = 0  # redundancy version of the *next* transmission


@dataclasses.dataclass
class _Job:
    """One pending transmission in a user's queue."""
    enq_tick: int  # when this attempt became schedulable
    job_id: int = -1  # mesh-unique transport-block-job id (conservation)
    harq: Optional[HarqProcess] = None  # None until first serve


@dataclasses.dataclass
class UserState:
    """Per-user closed-loop state: queue, channel, and link adaptation."""
    user_id: int
    snr_db: float
    mcs: int
    olla: float = 0.0  # OLLA accumulator; +-1 triggers an MCS walk
    backlog: collections.deque = dataclasses.field(
        default_factory=collections.deque
    )


@dataclasses.dataclass
class TickStats:
    """What one TTI tick did (the per-tick log of the closed loop)."""
    tick: int
    n_arrivals: int = 0
    n_served: int = 0
    n_miss: int = 0  # served slots whose queue latency beat the deadline
    backlog_after: int = 0


@dataclasses.dataclass
class ClosedLoopReport:
    """Aggregate report of one closed-loop serving run (one cell)."""
    ladder: str
    receiver: str
    n_users: int
    n_ticks: int
    batch_size: int
    max_retx: int
    deadline_ttis: int
    adapt: bool
    n_slots: int
    n_batches: int
    wall_s: float
    slots_per_sec: float
    n_arrivals: int
    deadline_miss_rate: float
    first_tx_bler: Optional[float]
    residual_bler: Optional[float]
    mean_harq_rounds: Optional[float]
    blocks_delivered: int
    blocks_lost: int
    goodput_bits_per_sec: float
    # delivered payload bits per TTI tick: the channel-time goodput —
    # wall-clock-free, so runs with different per-rung pipeline costs
    # (e.g. adaptive vs fixed MCS) compare apples-to-apples
    goodput_bits_per_tti: float
    mcs_occupancy: dict  # rung scenario name -> fraction of served slots
    backlog_left: int
    harq_open: int  # HARQ buffers still allocated at the end of the run
    # modeled energy, occupancy-weighted over the rung pipelines
    precision: str = "fp32"
    energy_uj_per_slot: Optional[float] = None
    gops_per_watt: Optional[float] = None
    l1_residency: Optional[float] = None
    # inter-cell mobility (mesh runs only; zero on a single cell):
    # users migrated in/out of this cell and new-data jobs shed when the
    # whole ladder group was saturated
    cell: str = ""
    handover_in: int = 0
    handover_out: int = 0
    jobs_shed: int = 0
    # fault-tolerance accounting (supervised runs only; all zero on a
    # clean unsupervised run so reports stay field-for-field comparable)
    faults: int = 0
    degraded_batches: int = 0
    quarantined_batches: int = 0
    quarantine_ticks: int = 0
    crashes: int = 0
    jobs_failed: int = 0
    # AOT executable accounting (exec_registry): compile wall time, true
    # XLA compiles vs cache hits, and first vs steady-state tick latency
    compile_time_s: float = 0.0
    executables_compiled: int = 0
    cache_hits: int = 0
    first_tick_s: Optional[float] = None
    steady_tick_s: Optional[float] = None

    def summary(self) -> str:
        parts = [
            f"closed-loop[{self.ladder}]: {self.n_slots} slots / "
            f"{self.n_ticks} TTIs in {self.wall_s:.3f}s "
            f"({self.slots_per_sec:.1f} slots/s, batch={self.batch_size})",
            f"miss={self.deadline_miss_rate:.3f}",
        ]
        if self.first_tx_bler is not None:
            parts.append(f"1tx-BLER={self.first_tx_bler:.4f}")
        if self.residual_bler is not None:
            parts.append(f"resid-BLER={self.residual_bler:.4f}")
        if self.mean_harq_rounds is not None:
            parts.append(f"rounds={self.mean_harq_rounds:.2f}")
        parts.append(f"goodput={self.goodput_bits_per_sec/1e6:.2f} Mbit/s")
        if self.gops_per_watt is not None:
            parts.append(
                f"{self.precision}: {self.gops_per_watt:.0f} GOPS/W"
            )
        if self.handover_in or self.handover_out or self.jobs_shed:
            parts.append(
                f"ho={self.handover_in}in/{self.handover_out}out "
                f"shed={self.jobs_shed}"
            )
        occ = " ".join(
            f"{name}:{frac:.2f}"
            for name, frac in sorted(self.mcs_occupancy.items())
        )
        parts.append(f"occ[{occ}]")
        return "  ".join(parts)


class JobCounter:
    """Monotone transport-block-job id allocator.

    Shared by every :class:`CellLoop` of a mesh so job ids stay unique
    across cells even as users migrate; ``n`` is the total issued so far,
    making the conservation invariant enumerable: the issued ids are
    exactly ``range(n)`` and each must end up finalized or queued.
    """

    def __init__(self):
        self.n = 0

    def __next__(self) -> int:
        i = self.n
        self.n += 1
        return i

    def __iter__(self):
        return self


def resolve_ladder(ladder):
    """Accept an MCSLadder, a registered ladder name, or a single coded
    LinkScenario (a one-rung ladder); return ``(name, rung scenarios)``."""
    from repro.phy.scenarios import LinkScenario, MCSLadder, get_ladder

    if isinstance(ladder, str):
        try:
            ladder = get_ladder(ladder)
        except KeyError:
            from repro.phy.scenarios import get_scenario

            ladder = get_scenario(ladder)
    if isinstance(ladder, LinkScenario):
        assert ladder.code is not None, (
            f"{ladder.name}: the closed loop needs a channel code "
            "(CRC ACK/NACK feedback)"
        )
        return ladder.name, [ladder]
    assert isinstance(ladder, MCSLadder), ladder
    return ladder.name, ladder.scenarios()


def occupancy_energy(occupancy, pipelines):
    """Occupancy-weighted modeled energy over rung pipelines.

    Returns ``(energy_uj_per_slot, gops_per_watt, l1_residency)`` —
    total modeled joules across every served slot / total ops at each
    rung's per-slot EnergyReport — or ``(None, None, None)`` when no
    served rung carries cycle estimators.
    """
    rung_reps = [
        (n, p.energy_report())
        for n, p in zip(occupancy, pipelines)
        if n > 0 and p.stage_cycles()
    ]
    if not rung_reps:
        return None, None, None
    tot_j = sum(n * er.total_j for n, er in rung_reps)
    tot_ops = sum(n * er.ops for n, er in rung_reps)
    tot_l1 = sum(n * er.l1_bytes for n, er in rung_reps)
    tot_dma = sum(n * er.dma_bytes for n, er in rung_reps)
    n_slots = sum(n for n, _ in rung_reps)
    return (
        tot_j / n_slots * 1e6,
        tot_ops / tot_j * 1e-9 if tot_j > 0 else 0.0,
        tot_l1 / (tot_l1 + tot_dma) if tot_l1 + tot_dma else 0.0,
    )


def _coded_slot_fn(scenario, retx: bool):
    """:func:`repro.phy.coding.make_coded_slot` at batch 1 as a function
    of one transmission's values: ``seed`` (the integer behind the slot's
    PRNG key), ``snr_db`` and, for a retransmission, ``rv`` and the pinned
    transport blocks ``info``.  Everything else comes from ``scenario``."""
    from repro.phy import coding

    def gen(args: dict) -> dict:
        key = jax.random.PRNGKey(args["seed"])
        scn = scenario.replace(snr_db=args["snr_db"])
        if retx:
            return coding.make_coded_slot(key, scn, 1, rv=args["rv"],
                                          info=args["info"])
        return coding.make_coded_slot(key, scn, 1, rv=0)

    return gen


class SlotGenerator:
    """Closed-loop slot generation as prebuilt compiled executables.

    Built eagerly, one coded slot is ~300 small device dispatches (CRC,
    QC-LDPC encode, rate matching, grid mapping, channel, noise, IFFT);
    here it is one call of an AOT executable acquired through the
    :class:`~repro.serve.exec_registry.ExecRegistry`.  Executables are
    keyed by what fixes the computation — grid, modem, code, interferer
    powers, Doppler, per-stream powers — and by the kind of transmission:
    new data (RV 0, fresh transport blocks) or a retransmission (the RV
    and the pinned blocks as arguments).  Key seed and SNR are arguments,
    so users at any SNR share one executable: at most two per rung.
    """

    def __init__(self, *, registry=None, stats: Optional[ExecStats] = None):
        self.registry = registry if registry is not None else get_registry()
        self.stats = stats if stats is not None else ExecStats()
        self._execs: dict = {}  # (shape key, retx) -> compiled generator

    @staticmethod
    def _shape(scenario) -> tuple:
        return (scenario.grid, scenario.modulation, scenario.code,
                tuple(scenario.interferer_db), scenario.doppler_rho,
                scenario.user_power_db)

    def _exec(self, scenario, retx: bool):
        """The resident executable, acquiring it first if absent;
        returns ``(executable, was_resident)``."""
        shape = self._shape(scenario)
        ex = self._execs.get((shape, retx))
        if ex is not None:
            return ex, True
        from repro.phy import coding

        code = scenario.code
        example = {"seed": np.int32(0),
                   "snr_db": np.float32(scenario.snr_db)}
        if retx:
            example["rv"] = np.int32(1)
            example["info"] = np.zeros(
                (1, coding.codewords_per_slot(scenario), code.k_info),
                np.int32,
            )
        key = ExecKey(
            scenario=f"coded_slot:{code.name}:{scenario.modulation}",
            receiver="slot_gen", precision="fp32", batch=1, lanes=0,
            backend=jax.default_backend(),
            variant=hashlib.blake2b(
                repr(shape).encode(), digest_size=8
            ).hexdigest(),
            schema="retx" if retx else "new",
        )
        ex = self.registry.acquire(
            key, _coded_slot_fn(scenario, retx), example, stats=self.stats
        )
        self._execs[(shape, retx)] = ex
        return ex, False

    def prebuild(self, scenario, max_retx: int) -> None:
        """Acquire every executable transmissions of ``scenario`` use."""
        self._exec(scenario, False)
        if max_retx > 0:
            self._exec(scenario, True)

    def __call__(self, scenario, seed: int, *, rv: int = 0,
                 info: Optional[np.ndarray] = None) -> tuple:
        """One batch-1 coded slot of ``scenario`` (its ``snr_db`` included)
        from key seed ``seed``: new data when ``info`` is None, else a
        retransmission of ``info`` at ``rv``.  Returns ``(slot,
        was_prebuilt)``; the slot has the keys, shapes and dtypes of
        :func:`~repro.phy.coding.make_coded_slot` with ``rv`` set."""
        retx = info is not None
        ex, resident = self._exec(scenario, retx)
        args = {"seed": np.int32(seed),
                "snr_db": np.float32(scenario.snr_db)}
        if retx:
            args["rv"] = np.int32(rv)
            args["info"] = np.asarray(info, np.int32)
        return ex(args), resident


class CellLoop:
    """The per-cell closed-loop state machine (no pipeline execution).

    Owns everything about one logical cell *except* running pipelines:
    per-user queues and link-adaptation state, Poisson arrivals, HARQ
    soft buffers and ACK/NACK feedback, batch planning under the pool's
    per-TTI capacity, and the aggregate counters behind
    :class:`ClosedLoopReport`.  :class:`SlotScheduler` drives one of
    these through per-rung :class:`BatchRunner` executables; the mesh
    closed loop (:class:`repro.serve.cell_mesh.MeshSlotScheduler`) drives
    many in TTI lockstep through sharded ``jit(vmap(pipeline))`` steps.
    Sharing the state machine is what makes a 1-cell mesh run and a
    single-cell run bit-identical on the same seed.

    All randomness — arrivals, SNR spread, and the jax keys behind slot
    generation — draws from the single ``rng`` stream (:func:`cell_rng`),
    so a cell's whole trajectory is reproducible from ``(seed, cell)``.
    """

    def __init__(self, rungs, *, name: str = "cell0",
                 rng: np.random.Generator, n_users: int = 4,
                 batch_size: int = 4, arrival_rate: float = 1.0,
                 max_retx: int = 2, deadline_ttis: int = 4,
                 max_batches_per_tick: Optional[int] = None,
                 adapt: bool = True, target_bler: float = 0.1,
                 olla_step: float = 0.1, init_mcs: int = 0,
                 snr_db: Optional[float] = None,
                 snr_spread_db: float = 0.0,
                 interferer_db: tuple = (), uid_base: int = 0,
                 job_ids=None, slot_gen: Optional[SlotGenerator] = None):
        self.name = name
        self.rungs = list(rungs)
        self.rng = rng
        # compiled slot generation, shared by every loop of a scheduler
        self.slot_gen = slot_gen if slot_gen is not None else SlotGenerator()
        # co-channel interferer powers (dB rel. signal) appended to every
        # served rung's own interferer list — the mesh's coupling wiring
        # sets this from same-group neighbor tx powers.  Empty () leaves
        # slot generation byte-identical to an uncoupled cell.
        self.interferer_db = tuple(interferer_db)
        self.batch_size = batch_size
        self.arrival_rate = arrival_rate
        self.max_retx = max_retx
        self.deadline_ttis = deadline_ttis
        self.max_batches_per_tick = max_batches_per_tick
        self.adapt = adapt and len(self.rungs) > 1
        self.target_bler = target_bler
        self.olla_up = olla_step
        self.olla_down = olla_step * (1.0 - target_bler) / target_bler
        # job ids come from a shared counter in a mesh so they are unique
        # across cells even as users migrate
        self._job_ids = JobCounter() if job_ids is None else job_ids

        init_mcs = min(init_mcs, len(self.rungs) - 1)
        base_snr = self.rungs[init_mcs].snr_db if snr_db is None else snr_db
        self.users = [
            UserState(
                user_id=uid_base + i,
                snr_db=float(base_snr + self.rng.uniform(
                    -snr_spread_db, snr_spread_db
                )),
                mcs=init_mcs,
            )
            for i in range(n_users)
        ]
        self.now = 0
        self.tick_log: list[TickStats] = []
        self.n_batches = 0  # compiled batches planned+served for this cell
        # aggregate counters
        self._arrivals = 0
        self._served = 0
        self._missed = 0
        self._first_tx_blocks = 0
        self._first_tx_errors = 0
        self._delivered = [0] * len(self.rungs)  # blocks per rung
        self._lost = 0
        self._rounds: list[int] = []  # per finalized process
        self._occupancy = [0] * len(self.rungs)  # served slots per rung
        # conservation bookkeeping: every job id ends in exactly one of
        # finalized (delivered / exhausted / shed) or some cell's backlog
        self.finalized_jobs: list[int] = []
        self.handover_in = 0
        self.handover_out = 0
        self.jobs_shed = 0

    # -- traffic ----------------------------------------------------------
    def _new_job(self) -> _Job:
        self._arrivals += 1
        return _Job(enq_tick=self.now, job_id=next(self._job_ids))

    def inject_backlog(self, n_per_user: int) -> None:
        """Enqueue ``n_per_user`` new-data jobs for every user at the
        current tick (deterministic traffic for tests/benchmarks)."""
        for u in self.users:
            for _ in range(n_per_user):
                u.backlog.append(self._new_job())

    def arrive(self, stats: TickStats) -> None:
        if self.arrival_rate <= 0:
            return
        for u in self.users:
            for _ in range(int(self.rng.poisson(self.arrival_rate))):
                u.backlog.append(self._new_job())
                stats.n_arrivals += 1

    # -- slot construction ------------------------------------------------
    def prebuild_slots(self) -> None:
        """Acquire the slot generator's executables for every rung, so no
        transmission this loop sends compiles."""
        for scn in self.rungs:
            self.slot_gen.prebuild(self._tx_scenario(scn, scn.snr_db),
                                   self.max_retx)

    def make_slot(self, user: UserState, job: _Job, mcs: int) -> dict:
        """Build the (re)transmission slot for one job.

        New data draws fresh transport blocks at the planned MCS (the
        batch's rung) and allocates the HARQ process; retransmissions
        re-encode the pinned process's blocks at its next RV over a
        fresh channel realization, with the combined-LLR buffer riding
        as the prior.  Either is one call of a compiled
        :class:`SlotGenerator` executable.
        """
        from repro.phy import coding

        with span("serve.make_slot",
                  retx=int(job.harq is not None)) as sp:
            seed = rng_seed(self.rng)
            if job.harq is None:
                scn = self.rungs[mcs]
                n_cw = coding.codewords_per_slot(scn)
                slot, prebuilt = self.slot_gen(
                    self._tx_scenario(scn, user.snr_db), seed
                )
                job.harq = HarqProcess(
                    mcs=mcs,
                    info=np.asarray(slot["info_bits"]),
                    prior=np.zeros(
                        (1, n_cw, scn.code.n_mother), np.float32
                    ),
                    acked=np.zeros(n_cw, bool),
                )
            else:
                h = job.harq
                scn = self.rungs[h.mcs]  # retx pins the MCS of the first tx
                slot, prebuilt = self.slot_gen(
                    self._tx_scenario(scn, user.snr_db), seed,
                    rv=h.rv, info=h.info,
                )
            sp.set_metadata(compiled=int(prebuilt))
            slot["prior_llr"] = job.harq.prior
            return slot

    def _tx_scenario(self, scn, snr_db: float):
        """The per-transmission scenario: the rung at the user's SNR, plus
        any cell-level co-channel interference on top of the rung's own."""
        if self.interferer_db:
            return scn.replace(
                snr_db=snr_db,
                interferer_db=tuple(scn.interferer_db) + self.interferer_db,
            )
        return scn.replace(snr_db=snr_db)

    # -- feedback ---------------------------------------------------------
    def serve_feedback(self, user: UserState, job: _Job, mcs: int,
                       crc_ok: np.ndarray, cw_llr: np.ndarray,
                       stats: TickStats) -> None:
        """Record one served slot and ACK/NACK its transport blocks."""
        self._occupancy[mcs] += 1
        self._served += 1
        stats.n_served += 1
        if self.now - job.enq_tick > self.deadline_ttis:
            self._missed += 1
            stats.n_miss += 1
        self._feedback(user, job, crc_ok, cw_llr)

    def _feedback(self, user: UserState, job: _Job, crc_ok: np.ndarray,
                  cw_llr: np.ndarray) -> None:
        """ACK/NACK one served slot: finalize, requeue, or exhaust."""
        h = job.harq
        h.n_tx += 1
        first_tx = h.n_tx == 1
        ok = h.acked | crc_ok
        if first_tx:
            self._first_tx_blocks += crc_ok.size
            self._first_tx_errors += int((~crc_ok).sum())
            if self.adapt:
                self._olla(user, bool(crc_ok.all()))
        if ok.all():
            self._delivered[h.mcs] += int(ok.size)
            self._rounds.append(h.n_tx)
            self.finalized_jobs.append(job.job_id)
            job.harq = None  # buffer freed
        elif h.n_tx > self.max_retx:
            self._delivered[h.mcs] += int(ok.sum())
            self._lost += int((~ok).sum())
            self._rounds.append(h.n_tx)
            self.finalized_jobs.append(job.job_id)
            job.harq = None  # block lost, buffer freed
        else:
            h.acked = ok
            h.prior = np.asarray(cw_llr, np.float32)
            h.rv += 1
            # retransmissions queue ahead of the user's new data
            user.backlog.appendleft(
                dataclasses.replace(job, enq_tick=self.now)
            )

    def _olla(self, user: UserState, ack: bool) -> None:
        """Outer-loop link adaptation: asymmetric ACK/NACK steps with
        zero drift at the target first-transmission BLER; crossing +-1
        walks the MCS one rung and resets the accumulator."""
        user.olla += self.olla_up if ack else -self.olla_down
        if user.olla >= 1.0:
            if user.mcs < len(self.rungs) - 1:
                user.mcs += 1
            user.olla = 0.0
        elif user.olla <= -1.0:
            if user.mcs > 0:
                user.mcs -= 1
            user.olla = 0.0

    # -- planning ---------------------------------------------------------
    def plan_batches(self) -> list:
        """Pick this tick's transmissions and form its compiled batches.

        One slot per user per TTI (its oldest job).  Batches group by
        (MCS, channel SNR): MCS picks the rung's compiled executable, and
        the SNR must be batch-uniform because ``noise_var`` is scalar
        side info shared by a whole batch (same constraint as a mesh
        lane) — mixing SNRs would mis-scale every non-head user's LLRs.
        Batches are capped at ``max_batches_per_tick`` (compiled-batch
        units — the pool's per-TTI capacity), oldest job first; jobs that
        don't fit go back to their user's queue head and wait.
        """
        active = [u for u in self.users if u.backlog]
        active.sort(key=lambda u: u.backlog[0].enq_tick)
        by_key: dict[tuple, list] = {}
        for u in active:
            job = u.backlog.popleft()
            mcs = job.harq.mcs if job.harq is not None else u.mcs
            by_key.setdefault((mcs, u.snr_db), []).append((u, job))
        batches = []
        for (mcs, _snr), pairs in by_key.items():
            for i in range(0, len(pairs), self.batch_size):
                batches.append((mcs, pairs[i : i + self.batch_size]))
        batches.sort(key=lambda b: min(j.enq_tick for _, j in b[1]))
        cap = self.max_batches_per_tick
        if cap is not None and len(batches) > cap:
            for _mcs, pairs in batches[cap:]:
                for u, job in pairs:  # one job per user -> head restore
                    u.backlog.appendleft(job)
            batches = batches[:cap]
        return batches

    def end_tick(self, stats: TickStats) -> TickStats:
        stats.backlog_after = self.backlog
        self.tick_log.append(stats)
        self.now += 1
        return stats

    # -- mobility (driven by the mesh scheduler) --------------------------
    def pending_jobs(self) -> int:
        return sum(len(u.backlog) for u in self.users)

    def capacity_jobs(self) -> float:
        """Jobs this cell can serve within its deadline budget — the
        saturation threshold of the handover/shedding policy.  Unlimited
        pool capacity means the cell never saturates."""
        if self.max_batches_per_tick is None:
            return float("inf")
        return (self.max_batches_per_tick * self.batch_size
                * (self.deadline_ttis + 1))

    def shed_tail(self, n: int) -> list[int]:
        """Drop up to ``n`` not-yet-started jobs from the backlog tails.

        Only new-data jobs are sheddable — a job with an in-flight HARQ
        process has soft state and delivery history that must finalize
        through feedback.  Returns the shed job ids (they finalize here,
        keeping conservation exact)."""
        shed = []
        for u in sorted(self.users, key=lambda u: -len(u.backlog)):
            while len(shed) < n and u.backlog and \
                    u.backlog[-1].harq is None:
                job = u.backlog.pop()
                shed.append(job.job_id)
        self.finalized_jobs.extend(shed)
        self.jobs_shed += len(shed)
        return shed

    # -- reporting --------------------------------------------------------
    @property
    def backlog(self) -> int:
        return sum(len(u.backlog) for u in self.users)

    @property
    def harq_open(self) -> int:
        """HARQ soft buffers currently allocated (in-flight processes)."""
        return sum(
            1 for u in self.users for j in u.backlog if j.harq is not None
        )

    def good_bits(self) -> float:
        return sum(
            d * s.code.k_info for d, s in zip(self._delivered, self.rungs)
        )

    def report(self, *, ladder_name: str, receiver: str, pipelines,
               wall_s: float, n_batches: int) -> ClosedLoopReport:
        wall_safe = max(wall_s, 1e-9)
        finalized = self._lost + sum(self._delivered)
        good_bits = self.good_bits()
        total_occ = max(sum(self._occupancy), 1)
        energy, gops_w, l1_res = occupancy_energy(
            self._occupancy, pipelines
        )
        return ClosedLoopReport(
            ladder=ladder_name,
            receiver=receiver,
            n_users=len(self.users),
            n_ticks=self.now,
            batch_size=self.batch_size,
            max_retx=self.max_retx,
            deadline_ttis=self.deadline_ttis,
            adapt=self.adapt,
            n_slots=self._served,
            n_batches=n_batches,
            wall_s=wall_s,
            slots_per_sec=self._served / wall_safe,
            n_arrivals=self._arrivals,
            deadline_miss_rate=(
                self._missed / self._served if self._served else 0.0
            ),
            first_tx_bler=(
                self._first_tx_errors / self._first_tx_blocks
                if self._first_tx_blocks else None
            ),
            residual_bler=(
                self._lost / finalized if finalized else None
            ),
            mean_harq_rounds=(
                float(np.mean(self._rounds)) if self._rounds else None
            ),
            blocks_delivered=int(sum(self._delivered)),
            blocks_lost=self._lost,
            goodput_bits_per_sec=good_bits / wall_safe,
            goodput_bits_per_tti=good_bits / max(self.now, 1),
            mcs_occupancy={
                s.name: self._occupancy[i] / total_occ
                for i, s in enumerate(self.rungs)
            },
            backlog_left=self.backlog,
            harq_open=self.harq_open,
            precision=pipelines[0].precision,
            energy_uj_per_slot=energy,
            gops_per_watt=gops_w,
            l1_residency=l1_res,
            cell=self.name,
            handover_in=self.handover_in,
            handover_out=self.handover_out,
            jobs_shed=self.jobs_shed,
        )


# ---------------------------------------------------------------------------
# Single-cell closed-loop frontend
# ---------------------------------------------------------------------------

class SlotScheduler:
    """TTI-clocked closed-loop slot scheduler over an MCS ladder.

    A thin execution frontend over one :class:`CellLoop`: the state
    machine plans each tick's batches, this class runs them through the
    per-rung :class:`BatchRunner` executables and feeds the CRC results
    back.  For the many-cell version sharded over a device mesh see
    :class:`repro.serve.cell_mesh.MeshSlotScheduler`.

    Parameters
    ----------
    ladder: an :class:`~repro.phy.scenarios.MCSLadder`, a registered
        ladder name, or a single coded :class:`LinkScenario` (fixed MCS,
        a one-rung ladder).
    n_users: users in the cell; each keeps its own queue, HARQ state,
        and link-adaptation state.
    batch_size: slots per compiled pipeline invocation (per rung).
    receiver / options: forwarded to the pipeline builder once per rung.
    pipelines: prebuilt per-rung pipelines (skips building; lets sweeps
        reuse compiled executables across scheduler instances).
    arrival_rate: Poisson mean of new slot arrivals per user per TTI.
    max_retx: HARQ retransmissions after the first transmission before a
        block is declared lost and its buffer freed.
    deadline_ttis: queue-latency budget; a served slot that waited more
        ticks than this counts as a TTI-deadline miss.
    max_batches_per_tick: pool capacity — compiled batches the cell can
        run inside one TTI (None = serve every active user each tick).
    adapt / target_bler / olla_step: OLLA link adaptation.  On ACK the
        accumulator rises by ``olla_step``, on NACK it falls by
        ``olla_step * (1 - target_bler) / target_bler`` (zero drift at
        the target), and crossing +-1 walks the user one rung up/down.
    snr_db: the users' channel SNR (defaults to the lowest rung's
        operating point); snr_spread_db spreads users uniformly around it.
    interferer_db: cell-level co-channel interferer powers (dB relative
        to the signal), appended to every rung's own interferer list for
        each served slot.
    seed: the single seed behind every random draw (arrivals, SNR
        spread, slot/channel/noise realizations) via :func:`cell_rng` —
        two schedulers with equal config + seed replay identically.
    prebuild: AOT-compile every rung's executable, and the slot
        generator's, at construction through the
        :class:`~repro.serve.exec_registry.ExecRegistry` (all cache hits
        on a warm persistent cache); ``False`` defers each to its first
        use.
    registry: explicit :class:`ExecRegistry` (default: the process-wide
        registry, shared with every other engine in the process).
    """

    def __init__(self, ladder, *, n_users: int = 4, batch_size: int = 4,
                 receiver: str = "classical", options: Optional[dict] = None,
                 pipelines: Optional[list] = None,
                 arrival_rate: float = 1.0, max_retx: int = 2,
                 deadline_ttis: int = 4,
                 max_batches_per_tick: Optional[int] = None,
                 adapt: bool = True, target_bler: float = 0.1,
                 olla_step: float = 0.1, init_mcs: int = 0,
                 snr_db: Optional[float] = None,
                 snr_spread_db: float = 0.0,
                 interferer_db: tuple = (), seed: int = 0,
                 prebuild: bool = True, registry=None):
        self.ladder_name, self.rungs = resolve_ladder(ladder)
        self.receiver = receiver
        self.batch_size = batch_size

        if pipelines is None:
            pipelines = [
                _link.build_pipeline(receiver, s, **(options or {}))
                for s in self.rungs
            ]
        assert len(pipelines) == len(self.rungs)
        self.runners = [
            BatchRunner(p, batch_size, registry=registry) for p in pipelines
        ]
        self.tick_times: list[float] = []
        if prebuild:
            # AOT-populate every rung's executable before the first TTI:
            # with a warm persistent cache this is all cache hits, so a
            # fresh process reaches its first tick with zero XLA compiles
            for scn, runner in zip(self.rungs, self.runners):
                runner.prepare(template_batch(scn, batch_size, harq=True))

        self.loop = CellLoop(
            self.rungs, rng=cell_rng(seed), n_users=n_users,
            batch_size=batch_size, arrival_rate=arrival_rate,
            max_retx=max_retx, deadline_ttis=deadline_ttis,
            max_batches_per_tick=max_batches_per_tick, adapt=adapt,
            target_bler=target_bler, olla_step=olla_step,
            init_mcs=init_mcs, snr_db=snr_db,
            snr_spread_db=snr_spread_db, interferer_db=interferer_db,
            slot_gen=SlotGenerator(registry=registry),
        )
        if prebuild:
            self.loop.prebuild_slots()
        self.ledger = SlotLedger()

    # delegation: the state machine is the source of truth
    @property
    def users(self):
        return self.loop.users

    @property
    def tick_log(self):
        return self.loop.tick_log

    @property
    def now(self) -> int:
        return self.loop.now

    @property
    def max_retx(self) -> int:
        return self.loop.max_retx

    @property
    def adapt(self) -> bool:
        return self.loop.adapt

    @property
    def harq_open(self) -> int:
        return self.loop.harq_open

    def inject_backlog(self, n_per_user: int) -> None:
        self.loop.inject_backlog(n_per_user)

    def _plan_batches(self) -> list:
        return self.loop.plan_batches()

    # -- the TTI loop -----------------------------------------------------
    def tick(self) -> TickStats:
        """Advance one TTI: arrivals, batched serving, HARQ feedback."""
        loop = self.loop
        stats = TickStats(tick=loop.now)
        loop.arrive(stats)

        served_before = sum(r.wall_s for r in self.runners)
        n_before = sum(r.n_batches for r in self.runners)
        for mcs, pairs in loop.plan_batches():
            runner = self.runners[mcs]
            reqs = [
                self.ledger.new_request(
                    loop.make_slot(u, job, mcs), user_id=u.user_id
                )
                for u, job in pairs
            ]
            # a batch shares one SNR (plan_batches): its scenario gives
            # the filter's noise variance without reading the slots
            state = runner.run_batch(reqs, noise_var=loop._tx_scenario(
                self.rungs[mcs], pairs[0][0].snr_db).noise_var)
            loop.n_batches += 1
            crc_ok = np.asarray(state["crc_ok"])
            cw_llr = np.asarray(state["cw_llr"])
            for j, (u, job) in enumerate(pairs):
                loop.serve_feedback(
                    u, job, mcs, crc_ok[j].astype(bool),
                    cw_llr[j : j + 1], stats,
                )
        # first vs steady-state latency: only ticks that served a batch
        if sum(r.n_batches for r in self.runners) > n_before:
            self.tick_times.append(
                sum(r.wall_s for r in self.runners) - served_before
            )
        return loop.end_tick(stats)

    def run(self, n_ticks: int) -> ClosedLoopReport:
        for _ in range(n_ticks):
            self.tick()
        return self.report()

    # -- reporting --------------------------------------------------------
    def report(self) -> ClosedLoopReport:
        rep = self.loop.report(
            ladder_name=self.ladder_name,
            receiver=self.receiver,
            pipelines=[r.pipeline for r in self.runners],
            wall_s=sum(r.wall_s for r in self.runners),
            n_batches=sum(r.n_batches for r in self.runners),
        )
        stats = ExecStats().merge(self.loop.slot_gen.stats)
        for r in self.runners:
            stats.merge(r.exec_stats)
        first_s, steady_s = first_steady(self.tick_times)
        return dataclasses.replace(
            rep,
            compile_time_s=stats.compile_time_s,
            executables_compiled=stats.executables_compiled,
            cache_hits=stats.cache_hits,
            first_tick_s=first_s,
            steady_tick_s=steady_s,
        )
