"""Multi-cell sharded PHY slot serving over a jax device mesh.

The paper places TensorPool inside a densified base-station fleet: one
compute cluster multiplexes *many* cells' uplink traffic (AI-RAN style).
This module scales :class:`repro.serve.phy_engine.PhyServeEngine` past one
cell (both are thin frontends over the shared slot-scheduler core in
:mod:`repro.serve.runtime`: submit bookkeeping, slot stacking, metric
aggregation, and report construction all come from there): a
:class:`CellMeshEngine` instantiates N cells — each a registered
scenario + receiver pipeline — and drains their slot queues through
jit-sharded batched steps on a ``(cell, batch)`` device mesh
(:func:`repro.launch.mesh.make_cell_mesh`), using the logical-axis rules in
:mod:`repro.distributed.sharding` (``ACT_RULES_PHY``).

Execution model
---------------
* Cells are partitioned into **shape groups** by (receiver kind, grid,
  modulation, builder options).  All cells in a group share one
  :class:`~repro.phy.link.ReceiverPipeline` — and therefore one compiled
  executable — because nothing else about a scenario (SNR, Doppler,
  description) changes the receive computation.
* Each group step stacks slots as ``(n_lanes, batch, ...)`` and runs
  ``jit(vmap(pipeline._apply))`` with the cell axis sharded across the
  mesh's ``cell`` dimension and the slot batch across ``batch``.  Per-lane
  numerics are identical to the single-cell engine.
* Host->device staging is **double buffered**: while the device computes
  step *i*, the host stacks and transfers step *i+1* (the serving-level
  analogue of the paper's DMA/compute overlap).
* A **load-imbalance policy** keeps lanes busy.  ``balance="steal"``
  assigns lanes to the cells with the longest remaining queues each step
  (a hot cell may occupy several lanes, lane-granular work stealing);
  ``balance="pad"`` keeps one lane per cell and pads short lanes.
  Stealing is lane-granular because a lane shares one scalar
  ``noise_var`` — slots from different-SNR cells cannot mix in a lane.

Two frontends share this execution model:

* :class:`CellMeshEngine` — open loop: drain pre-submitted slot queues,
  one-shot, no feedback.
* :class:`MeshSlotScheduler` — closed loop at mesh scale: hundreds of
  logical cells advance in TTI lockstep, each owning a
  :class:`repro.serve.runtime.CellLoop` (per-cell HARQ buffer pools with
  combined-LLR state, OLLA link adaptation, Poisson arrivals).  Every
  tick, all cells' planned (MCS, RV) batches are bucketed per shape
  group and rung into fixed lane counts, staged host->device with the
  combining-LLR priors riding along as donated buffers, executed as
  sharded ``jit(vmap(pipeline._apply))`` steps, and the CRC results fan
  back out to each cell's HARQ feedback.  When a cell's pool capacity
  saturates its deadline budget, queued users hand over to the
  least-loaded sibling cell of the same ladder group — and when no
  sibling has headroom, not-yet-started jobs are shed from the queue
  tails (HARQ-active jobs always finalize through feedback).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional, Union

import jax
import numpy as np

from repro.distributed import sharding as shd
from repro.launch.mesh import make_cell_mesh
from repro.phy import link as _link
from repro.phy.scenarios import LinkScenario, get_scenario
from repro.serve.exec_registry import (
    ExecStats, PowerOfTwoBuckets, get_registry, slot_schema, template_slot,
)
from repro.serve.runtime import (
    BATCHED_KEYS, CellLoop, ClosedLoopReport, JobCounter, PhyServeReport,
    SlotGenerator, SlotLedger, SlotRequest, TTI_S, TickStats,
    build_serve_report, cell_rng, first_steady, make_traffic,
    occupancy_energy, resolve_ladder, stack_slots,
)
from repro.serve.trace import shape_counts, span, step_window, wait


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """Static description of one cell: scenario + receiver + options.

    ``options`` is a sorted tuple of (key, value) pairs forwarded to
    :func:`repro.phy.link.build_pipeline` — kept hashable so it can take
    part in the shape-group key.
    """
    name: str
    scenario: Union[str, LinkScenario]
    receiver: str = "classical"
    options: tuple = ()


def cell(name: str, scenario: Union[str, LinkScenario],
         receiver: str = "classical", **options) -> CellSpec:
    """Convenience constructor: ``cell("c0", "siso-qam16-snr12", "cevit")``.

    Builder options ride along in the shape-group key, so e.g.
    ``cell("c0", "mimo2x2-qam16-snr16", fused=True)`` serves that cell
    through the fused classical-receiver kernels (its own compiled group).
    """
    return CellSpec(name, scenario, receiver, tuple(sorted(options.items())))


@dataclasses.dataclass
class _Cell:
    spec: CellSpec
    scenario: LinkScenario
    queue: list = dataclasses.field(default_factory=list)
    served: list = dataclasses.field(default_factory=list)
    n_lane_steps: int = 0  # lanes this cell occupied across all steps


@dataclasses.dataclass
class _Lane:
    """One mesh lane of one step: up to ``batch`` slots of a single cell."""
    cell_idx: Optional[int]  # None = filler lane (results discarded)
    reqs: list = dataclasses.field(default_factory=list)
    pad: int = 0  # slots repeated from reqs[0] to reach the static batch


class _Group:
    """Cells sharing one pipeline/compiled step (same shapes + receiver).

    The step executables themselves live in the process's
    :class:`~repro.serve.exec_registry.ExecRegistry`; ``_execs`` caches
    the acquired handle per slot schema so dispatch is a dict lookup.
    """

    def __init__(self, pipeline: _link.ReceiverPipeline,
                 cell_idxs: list[int]):
        self.pipeline = pipeline
        self.cell_idxs = cell_idxs
        self._execs: dict = {}  # slot schema -> AOT-compiled step
        self._metrics = jax.jit(jax.vmap(
            lambda st: _link.slot_metrics(
                st, pipeline.scenario, per_slot=True
            )
        ))
        self.wall_s = 0.0
        self.n_steps = 0
        self.n_padded = 0
        self.n_stolen = 0


@dataclasses.dataclass
class MeshServeReport:
    """Aggregate + per-cell report of one multi-cell serving run.

    ``tti_utilization`` is the modeled TensorPool budget of the run: each
    group step costs its pipeline's concurrent-schedule milliseconds for a
    ``batch_size`` lane, groups run back-to-back, and the whole figure is
    normalized by the 1 ms TTI per step.  ``cells`` maps cell name to a
    :class:`~repro.serve.phy_engine.PhyServeReport` whose numbers are
    directly comparable to a single-cell run of the same traffic.
    """
    n_cells: int
    n_groups: int
    mesh_shape: tuple
    balance: str
    batch_size: int
    n_slots: int
    n_steps: int
    wall_s: float
    slots_per_sec: float
    ber: Optional[float]
    che_mse: Optional[float]
    tti_utilization: float
    fits_tti: bool
    n_padded: int
    n_stolen: int
    cells: dict  # name -> PhyServeReport
    # coded-link aggregates (None when no cell carries a channel code)
    bler: Optional[float] = None
    info_bits_per_sec: Optional[float] = None
    # modeled energy aggregated over the cells (total ops / total joules;
    # slot-weighted L1 residency) — per-cell figures live in ``cells``
    gops_per_watt: Optional[float] = None
    l1_residency: Optional[float] = None
    # AOT executable accounting (exec_registry): compile wall time, true
    # XLA compiles vs cache hits, and first vs steady-state step latency
    compile_time_s: float = 0.0
    executables_compiled: int = 0
    cache_hits: int = 0
    first_tick_s: Optional[float] = None
    steady_tick_s: Optional[float] = None

    def summary(self) -> str:
        parts = [
            f"mesh[{self.mesh_shape[0]}x{self.mesh_shape[1]}] "
            f"{self.n_cells} cells/{self.n_groups} groups "
            f"({self.balance}): {self.n_slots} slots in {self.wall_s:.3f}s "
            f"({self.slots_per_sec:.1f} slots/s, batch={self.batch_size}, "
            f"{self.n_steps} steps)"
        ]
        if self.ber is not None:
            parts.append(f"BER={self.ber:.4f}")
        if self.bler is not None:
            parts.append(f"BLER={self.bler:.4f}")
        if self.info_bits_per_sec is not None:
            parts.append(
                f"goodput={self.info_bits_per_sec/1e6:.2f} Mbit/s"
            )
        if self.che_mse is not None:
            parts.append(f"CHE-MSE={self.che_mse:.4f}")
        parts.append(
            f"TTI util={self.tti_utilization:.3f} (fits={self.fits_tti})"
        )
        if self.gops_per_watt is not None:
            parts.append(f"{self.gops_per_watt:.0f} GOPS/W")
        if self.n_padded or self.n_stolen:
            parts.append(
                f"padded={self.n_padded} stolen_lanes={self.n_stolen}"
            )
        return "  ".join(parts)

    def per_cell_summary(self) -> str:
        return "\n".join(
            f"  {name:16s} {rep.summary()}"
            for name, rep in sorted(self.cells.items())
        )


class CellMeshEngine:
    """Serve N cells' slot queues through sharded mesh steps.

    Parameters
    ----------
    cells: CellSpec list (see :func:`cell`).  Cell names must be unique.
    batch_size: slots per lane per step (static; short lanes are padded).
    mesh: a ``(cell, batch)`` jax mesh; defaults to
        :func:`make_cell_mesh` sized so every shape group shards evenly.
    balance: "steal" (lane-granular work stealing, default) or "pad"
        (one lane per cell, pad-only).
    prebuild: AOT-compile every group's step at construction through the
        :class:`~repro.serve.exec_registry.ExecRegistry` (cache hits on a
        warm persistent cache); ``False`` defers each group to its first
        served step — acquisition still happens outside the timed window.
    registry: explicit :class:`ExecRegistry` (default: process-wide).
    """

    def __init__(self, cells: list[CellSpec], *, batch_size: int = 4,
                 mesh=None, balance: str = "steal",
                 prebuild: bool = True, registry=None):
        if balance not in ("steal", "pad"):
            raise ValueError(f"unknown balance policy {balance!r}")
        names = [c.name for c in cells]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate cell names in {names}")
        self.batch_size = batch_size
        self.balance = balance
        self.cells: list[_Cell] = []
        for spec in cells:
            scn = (get_scenario(spec.scenario)
                   if isinstance(spec.scenario, str) else spec.scenario)
            self.cells.append(_Cell(spec=spec, scenario=scn))

        by_key: dict[tuple, list[int]] = {}
        for i, c in enumerate(self.cells):
            # the code is part of the receive computation (decode stage
            # structure), so coded cells only group with same-code cells
            key = (c.spec.receiver, c.scenario.grid, c.scenario.modulation,
                   c.scenario.code, c.spec.options)
            by_key.setdefault(key, []).append(i)
        self.groups: list[_Group] = []
        for key, idxs in by_key.items():
            first = self.cells[idxs[0]]
            pipeline = _link.build_pipeline(
                first.spec.receiver, first.scenario,
                **dict(first.spec.options),
            )
            self.groups.append(_Group(pipeline, idxs))

        if mesh is None:
            lanes = math.gcd(*(len(g.cell_idxs) for g in self.groups)) \
                if self.groups else 1
            mesh = make_cell_mesh(lanes)
        self.mesh = mesh
        self._ledger = SlotLedger()
        self.registry = registry if registry is not None else get_registry()
        self.exec_stats = ExecStats()
        self.step_times: list[float] = []
        if prebuild:
            for group in self.groups:
                self._group_step(group, self._template_staged(group))

    def _template_staged(self, group: _Group) -> dict:
        """A staged example step for ``group`` built from template slots —
        same staging path as serving, so avals/shardings match exactly."""
        scn = self.cells[group.cell_idxs[0]].scenario
        req = SlotRequest(user_id=-1, slot=template_slot(scn))
        lane = _Lane(cell_idx=None, reqs=[req], pad=self.batch_size - 1)
        return self._stage([lane] * len(group.cell_idxs))

    def _group_step(self, group: _Group, example: dict):
        """Acquire ``group``'s AOT step for ``example``'s slot schema
        (registry hit once resident; persistent-cache hit when cold)."""
        schema = slot_schema(example)
        step = group._execs.get(schema)
        if step is None:
            step = self.registry.acquire_pipeline_step(
                group.pipeline, example, batch=self.batch_size,
                lanes=len(group.cell_idxs), stats=self.exec_stats,
            )
            group._execs[schema] = step
        return step

    # -- traffic ----------------------------------------------------------
    def _cell(self, name: str) -> _Cell:
        for c in self.cells:
            if c.spec.name == name:
                return c
        raise KeyError(
            f"unknown cell {name!r}; have {[c.spec.name for c in self.cells]}"
        )

    def submit(self, cell_name: str, slot: dict,
               user_id: Optional[int] = None) -> SlotRequest:
        req = self._ledger.new_request(slot, user_id)
        self._cell(cell_name).queue.append(req)
        return req

    def submit_traffic(self, key: jax.Array,
                       n_slots: Union[int, dict]) -> dict:
        """Simulate per-cell arrivals.

        ``n_slots`` is either one count for every cell or a
        ``{cell_name: count}`` dict (use uneven counts to exercise the
        balance policy).  Returns ``{cell_name: [SlotRequest, ...]}``.
        """
        if isinstance(n_slots, int):
            n_slots = {c.spec.name: n_slots for c in self.cells}
        out = {}
        keys = jax.random.split(key, max(len(n_slots), 1))
        for kc, (name, n) in zip(keys, sorted(n_slots.items())):
            scn = self._cell(name).scenario
            out[name] = [
                self.submit(name, slot)
                for slot in (make_traffic(scn, kc, n) if n else [])
            ]
        return out

    # -- scheduling -------------------------------------------------------
    def _plan(self, group: _Group) -> list[list[_Lane]]:
        """Partition the group's queued slots into steps of static lanes."""
        B = self.batch_size
        queues = {i: list(self.cells[i].queue) for i in group.cell_idxs
                  if self.cells[i].queue}
        for i in group.cell_idxs:
            self.cells[i].queue = []
        n_lanes = len(group.cell_idxs)
        steps: list[list[_Lane]] = []
        while queues:
            lanes: list[_Lane] = []
            if self.balance == "steal":
                # hottest-queue-first lane assignment: a backlogged cell
                # may occupy several lanes this step
                for lane_j in range(n_lanes):
                    if not queues:
                        lanes.append(_Lane(cell_idx=None))
                        continue
                    i = max(queues, key=lambda i: len(queues[i]))
                    take, queues[i] = queues[i][:B], queues[i][B:]
                    if not queues[i]:
                        del queues[i]
                    if group.cell_idxs[lane_j] != i:
                        group.n_stolen += 1
                    lanes.append(_Lane(cell_idx=i, reqs=take,
                                       pad=B - len(take)))
            else:  # "pad": lane j always serves cell j
                for i in group.cell_idxs:
                    q = queues.get(i, [])
                    take, rest = q[:B], q[B:]
                    if rest:
                        queues[i] = rest
                    else:
                        queues.pop(i, None)
                    if take:
                        lanes.append(_Lane(cell_idx=i, reqs=take,
                                           pad=B - len(take)))
                    else:
                        lanes.append(_Lane(cell_idx=None))
            # filler lanes replay the first real lane (results discarded)
            donor = next(l for l in lanes if l.cell_idx is not None)
            for j, l in enumerate(lanes):
                if l.cell_idx is None:
                    lanes[j] = _Lane(cell_idx=None, reqs=list(donor.reqs),
                                     pad=donor.pad)
            group.n_padded += sum(
                l.pad for l in lanes if l.cell_idx is not None
            )
            steps.append(lanes)
        return steps

    # -- staging (host side; overlapped with device compute) --------------
    def _stage(self, lanes: list[_Lane]) -> dict:
        """Stack one step's slots to (n_lanes, batch, ...) sharded arrays."""
        per_lane = [
            stack_slots([r.slot for r in lane.reqs], lane.pad, xp=np)
            for lane in lanes
        ]
        stacked = {
            # batched keys gain the lane axis; per-cell side info (left
            # unstacked by stack_slots, from the lane head) just stacks
            k: np.stack([np.asarray(pl[k]) for pl in per_lane], axis=0)
            for k in per_lane[0]
        }
        shardings = shd.cell_slot_shardings(
            stacked, self.mesh, batched_keys=BATCHED_KEYS
        )
        return {
            k: jax.device_put(v, shardings[k]) for k, v in stacked.items()
        }

    # -- serving ----------------------------------------------------------
    def _record(self, group: _Group, lanes: list[_Lane], state: dict):
        metrics = {
            k: np.asarray(v) for k, v in group._metrics(state).items()
        }  # each (n_lanes, batch)
        for j, lane in enumerate(lanes):
            if lane.cell_idx is None:
                continue
            c = self.cells[lane.cell_idx]
            c.n_lane_steps += 1
            for s, req in enumerate(lane.reqs):
                req.metrics = {k: float(v[j, s]) for k, v in metrics.items()}
                req.done = True
                c.served.append(req)

    def run(self, warmup: bool = True) -> MeshServeReport:
        """Serve every queued slot on the mesh; returns the mesh report.

        Each group's steps run back-to-back; within a group, host staging
        of step *i+1* overlaps device compute of step *i*.  The group's
        AOT executable is acquired from the registry before the timed
        window opens (a no-op when prebuilt/resident), so throughput
        always measures the steady-state executable; ``warmup`` is kept
        for API compatibility and no longer re-executes the first step.
        """
        del warmup  # acquisition replaced warmup execution
        for group in self.groups:
            plan = self._plan(group)
            if not plan:
                continue
            staged = self._stage(plan[0])
            step = self._group_step(group, staged)
            t_group = 0.0
            for i, lanes in enumerate(plan):
                t0 = time.perf_counter()
                state = step(staged)  # async dispatch
                staged = (self._stage(plan[i + 1])
                          if i + 1 < len(plan) else None)
                state = wait(state)
                dt = time.perf_counter() - t0
                t_group += dt
                self.step_times.append(dt)
                self._record(group, lanes, state)
            group.wall_s += t_group
            group.n_steps += len(plan)
        return self._report()

    # -- reporting --------------------------------------------------------
    def _cell_report(self, group: _Group, c: _Cell) -> PhyServeReport:
        # the shared aggregation/report core (runtime.build_serve_report)
        # keeps per-cell numbers directly comparable to a single-cell run;
        # wall time is the whole group's (cells share its compiled steps)
        return build_serve_report(
            group.pipeline, c.scenario, [r.metrics for r in c.served],
            n_slots=len(c.served), n_batches=c.n_lane_steps,
            batch_size=self.batch_size, wall_s=group.wall_s,
        )

    def _report(self) -> MeshServeReport:
        cells = {}
        group_of = {i: g for g in self.groups for i in g.cell_idxs}
        for i, c in enumerate(self.cells):
            cells[c.spec.name] = self._cell_report(group_of[i], c)
        n_slots = sum(r.n_slots for r in cells.values())
        n_steps = sum(g.n_steps for g in self.groups)
        wall = sum(g.wall_s for g in self.groups)
        # modeled budget: group steps run back-to-back, one TTI per step
        model_ms = sum(
            g.n_steps
            * g.pipeline.tti_report(batch=self.batch_size)["concurrent_ms"]
            for g in self.groups
        )
        budget_ms = n_steps * TTI_S * 1e3
        util = model_ms / budget_ms if budget_ms else 0.0

        def slot_mean(metric):
            # per-slot mean (slot-weighted, matching PhyServeEngine's
            # aggregation), not a mean of per-cell means
            pairs = [(getattr(r, metric), r.n_slots)
                     for r in cells.values()
                     if getattr(r, metric) is not None and r.n_slots]
            total = sum(n for _, n in pairs)
            if not total:
                return None
            return float(sum(v * n for v, n in pairs) / total)

        # aggregate goodput: delivered payload bits across all coded
        # cells over the whole run's wall time
        good_bits = 0.0
        any_coded = False
        for c in self.cells:
            rep = cells[c.spec.name]
            if rep.bler is None or c.scenario.code is None:
                continue
            from repro.phy import coding

            any_coded = True
            good_bits += coding.goodput_bits(
                c.scenario, rep.bler, rep.n_slots
            )
        # energy-weighted efficiency = total modeled ops / total joules
        e_pairs = [
            (r.gops_per_watt, r.n_slots * r.energy_uj_per_slot)
            for r in cells.values()
            if r.gops_per_watt is not None and r.energy_uj_per_slot
            and r.n_slots
        ]
        tot_j = sum(j for _, j in e_pairs)
        gops_w = (
            sum(g * j for g, j in e_pairs) / tot_j if tot_j else None
        )
        first_s, steady_s = first_steady(self.step_times)
        return MeshServeReport(
            n_cells=len(self.cells),
            n_groups=len(self.groups),
            mesh_shape=tuple(self.mesh.devices.shape),
            balance=self.balance,
            batch_size=self.batch_size,
            n_slots=n_slots,
            n_steps=n_steps,
            wall_s=wall,
            slots_per_sec=n_slots / max(wall, 1e-9),
            ber=slot_mean("ber"),
            che_mse=slot_mean("che_mse"),
            tti_utilization=util,
            fits_tti=bool(util <= 1.0),
            n_padded=sum(g.n_padded for g in self.groups),
            n_stolen=sum(g.n_stolen for g in self.groups),
            cells=cells,
            bler=slot_mean("bler"),
            info_bits_per_sec=(good_bits / max(wall, 1e-9)
                               if any_coded else None),
            gops_per_watt=gops_w,
            l1_residency=slot_mean("l1_residency"),
            compile_time_s=self.exec_stats.compile_time_s,
            executables_compiled=self.exec_stats.executables_compiled,
            cache_hits=self.exec_stats.cache_hits,
            first_tick_s=first_s,
            steady_tick_s=steady_s,
        )


# ---------------------------------------------------------------------------
# Closed-loop serving at mesh scale
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClosedCellSpec:
    """Static description of one closed-loop cell.

    ``ladder`` is a registered MCS-ladder (or coded-scenario) name — kept
    a string so it can take part in the hashable shape-group key.  Cells
    sharing (ladder, receiver, options) form one ladder group: they share
    the per-rung pipelines and compiled mesh steps, and handover/load
    shedding moves users between them.

    ``tx_power_db`` / ``coupling_db`` model co-channel coupling between
    same-group neighbors: when ``coupling_db`` is set, every *other* cell
    in this cell's ladder group contributes an interferer at
    ``neighbor.tx_power_db + coupling_db`` dB relative to the served
    signal (appended to each rung's own interferer list at slot
    generation).  Interference never enters the shape-group key — coupled
    and uncoupled cells compile the same mesh steps — and the default
    ``coupling_db=None`` leaves trajectories byte-identical to an
    uncoupled mesh.
    """
    name: str
    ladder: str
    n_users: int = 4
    arrival_rate: float = 1.0
    snr_db: Optional[float] = None
    snr_spread_db: float = 0.0
    init_mcs: int = 0
    receiver: str = "classical"
    options: tuple = ()
    tx_power_db: float = 0.0
    coupling_db: Optional[float] = None


def closed_cell(name: str, ladder: str, receiver: str = "classical",
                *, n_users: int = 4, arrival_rate: float = 1.0,
                snr_db: Optional[float] = None, snr_spread_db: float = 0.0,
                init_mcs: int = 0, tx_power_db: float = 0.0,
                coupling_db: Optional[float] = None,
                **options) -> ClosedCellSpec:
    """Convenience constructor mirroring :func:`cell` for closed loops."""
    return ClosedCellSpec(
        name, ladder, n_users=n_users, arrival_rate=arrival_rate,
        snr_db=snr_db, snr_spread_db=snr_spread_db, init_mcs=init_mcs,
        receiver=receiver, options=tuple(sorted(options.items())),
        tx_power_db=tx_power_db, coupling_db=coupling_db,
    )


@dataclasses.dataclass
class _ClosedLane:
    """One mesh lane of one closed-loop step: one cell's planned batch."""
    cell_idx: Optional[int]  # None = filler lane (results discarded)
    pairs: list = dataclasses.field(default_factory=list)  # (user, job)
    slots: list = dataclasses.field(default_factory=list)
    pad: int = 0


class _LadderGroup:
    """Cells sharing one MCS ladder + receiver: per-rung pipelines whose
    compiled mesh steps live in the process's
    :class:`~repro.serve.exec_registry.ExecRegistry`, cached here per
    (rung, lane bucket, slot schema) so dispatch is a dict lookup.

    ``donate`` marks the staged batch (arg 0, carrying the combining-LLR
    priors) for donation on accelerator backends so XLA may fold the
    prior+derate accumulation into the staging buffer in place (donation
    is a no-op warning on cpu, so it is gated off there).
    """

    def __init__(self, ladder_name: str, rungs, receiver: str,
                 options: dict, cell_idxs: list[int], donate: bool):
        self.ladder_name = ladder_name
        self.rungs = rungs
        self.receiver = receiver
        self.cell_idxs = cell_idxs
        self.donate = donate
        self.pipelines = [
            _link.build_pipeline(receiver, s, **options) for s in rungs
        ]
        self._execs: dict = {}  # (mcs, bucket, schema) -> AOT step


@dataclasses.dataclass
class MeshClosedLoopReport:
    """Aggregate + per-cell report of a mesh-scale closed-loop run.

    ``cells`` maps cell name to a
    :class:`~repro.serve.runtime.ClosedLoopReport` directly comparable to
    a single-cell :class:`~repro.serve.runtime.SlotScheduler` run of the
    same seeded traffic (per-cell wall time is the shared mesh wall: all
    cells ride the same compiled steps).
    """
    n_cells: int
    n_groups: int
    mesh_shape: tuple
    batch_size: int
    n_users: int
    n_ticks: int
    max_retx: int
    n_slots: int
    n_steps: int
    n_filler_lanes: int
    wall_s: float
    slots_per_sec: float
    n_arrivals: int
    deadline_miss_rate: float
    first_tx_bler: Optional[float]
    residual_bler: Optional[float]
    mean_harq_rounds: Optional[float]
    blocks_delivered: int
    blocks_lost: int
    jobs_shed: int
    handovers: int
    goodput_bits_per_sec: float
    goodput_bits_per_tti: float
    backlog_left: int
    harq_open: int
    precision: str = "fp32"
    energy_uj_per_slot: Optional[float] = None
    gops_per_watt: Optional[float] = None
    l1_residency: Optional[float] = None
    # fault-tolerance accounting (supervised runs only; all zero on a
    # clean unsupervised run so reports stay field-for-field comparable)
    faults_injected: int = 0
    step_retries: int = 0
    degraded_batches: int = 0
    quarantined_batches: int = 0
    batches_deferred: int = 0
    ticks_over_budget: int = 0
    cell_quarantines: int = 0
    crashes: int = 0
    recoveries: int = 0
    jobs_failed: int = 0
    # AOT executable accounting (exec_registry): compile wall time, true
    # XLA compiles vs cache hits, and first vs steady-state tick latency
    compile_time_s: float = 0.0
    executables_compiled: int = 0
    cache_hits: int = 0
    first_tick_s: Optional[float] = None
    steady_tick_s: Optional[float] = None
    cells: dict = dataclasses.field(default_factory=dict)

    def summary(self) -> str:
        parts = [
            f"mesh-closed[{self.mesh_shape[0]}x{self.mesh_shape[1]}] "
            f"{self.n_cells} cells/{self.n_groups} groups: "
            f"{self.n_slots} slots / {self.n_ticks} TTIs in "
            f"{self.wall_s:.3f}s ({self.slots_per_sec:.1f} slots/s, "
            f"batch={self.batch_size}, {self.n_steps} steps)",
            f"miss={self.deadline_miss_rate:.3f}",
        ]
        if self.first_tx_bler is not None:
            parts.append(f"1tx-BLER={self.first_tx_bler:.4f}")
        if self.residual_bler is not None:
            parts.append(f"resid-BLER={self.residual_bler:.4f}")
        parts.append(f"goodput={self.goodput_bits_per_sec/1e6:.2f} Mbit/s")
        if self.gops_per_watt is not None:
            parts.append(
                f"{self.precision}: {self.gops_per_watt:.0f} GOPS/W"
            )
        if self.handovers or self.jobs_shed:
            parts.append(
                f"handovers={self.handovers} shed={self.jobs_shed}"
            )
        if self.faults_injected or self.crashes or self.jobs_failed:
            parts.append(
                f"faults={self.faults_injected} crashes={self.crashes} "
                f"recovered={self.recoveries} failed={self.jobs_failed}"
            )
        if self.executables_compiled or self.cache_hits:
            parts.append(
                f"compile={self.compile_time_s:.2f}s "
                f"({self.executables_compiled}x/{self.cache_hits}hit)"
            )
        return "  ".join(parts)

    def per_cell_summary(self) -> str:
        return "\n".join(
            f"  {name:16s} {rep.summary()}"
            for name, rep in sorted(self.cells.items())
        )


class MeshSlotScheduler:
    """TTI-lockstep closed-loop scheduler for many cells on one mesh.

    The mesh-scale sibling of
    :class:`repro.serve.runtime.SlotScheduler`: every cell owns a
    :class:`~repro.serve.runtime.CellLoop` (the shared per-cell state
    machine — queues, HARQ pools, OLLA), and each global tick advances
    all of them in lockstep:

    1. **arrive** — every cell draws its Poisson arrivals from its own
       :func:`~repro.serve.runtime.cell_rng` stream (cell ``i`` of seed
       ``s`` replays exactly as a single-cell run seeded ``(s, i)``).
    2. **rebalance** — within each ladder group, cells whose pending
       jobs exceed their pool capacity
       (:meth:`~repro.serve.runtime.CellLoop.capacity_jobs`) hand whole
       users over to the least-loaded sibling with headroom; if no
       sibling has headroom, not-yet-started jobs are shed from queue
       tails (HARQ-active jobs are never shed — their soft state must
       finalize through feedback).
    3. **plan** — each cell forms its (MCS, SNR) batches; batches bucket
       per (ladder group, rung) into mesh lanes, padded with filler
       lanes to the pluggable :class:`BucketPolicy`'s lane bucket
       (:class:`PowerOfTwoBuckets` by default — at most log2(lanes) step
       shapes per (group, rung); see also :class:`FixedBuckets` and
       :class:`CostModelBuckets`).  Every step executable is owned by
       the process's :class:`~repro.serve.exec_registry.ExecRegistry`,
       AOT-populated at construction (``prebuild=True``) and backed by
       the persistent compilation cache, so a warm process restart
       reaches its first TTI with zero new XLA compilations.
    4. **serve** — each bucket stages host-side (per-lane
       :func:`stack_slots`, lane stack, ``cell_slot_shardings``,
       ``device_put``) and runs the rung's ``jit(vmap(pipeline._apply))``
       step; staging of bucket *k+1* overlaps device compute of bucket
       *k*, and the staged batch (carrying the combined-LLR priors) is
       donated on accelerator backends.
    5. **feedback** — CRC results fan back to each lane's cell:
       ACK/NACK, HARQ combine-buffer accumulate/free, OLLA walk.

    Transport-block jobs draw ids from one shared
    :class:`~repro.serve.runtime.JobCounter`, so conservation is
    checkable mesh-wide even across handover: issued ids ==
    finalized ids + queued ids, exactly once each.
    """

    def __init__(self, cells: list[ClosedCellSpec], *,
                 batch_size: int = 4, mesh=None, max_retx: int = 2,
                 deadline_ttis: int = 4,
                 max_batches_per_tick: Optional[int] = None,
                 adapt: bool = True, target_bler: float = 0.1,
                 olla_step: float = 0.1, seed: int = 0,
                 bucket_policy=None, registry=None,
                 prebuild: bool = True):
        names = [c.name for c in cells]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate cell names in {names}")
        self.batch_size = batch_size
        self.max_retx = max_retx
        self.specs = list(cells)
        self.job_counter = JobCounter()
        # loop-construction parameters, kept so a crashed cell's loop can
        # be rebuilt from its spec (see _make_loop / the Supervisor)
        self.seed = seed
        self.deadline_ttis = deadline_ttis
        self.max_batches_per_tick = max_batches_per_tick
        self.adapt = adapt
        self.target_bler = target_bler
        self.olla_step = olla_step

        donate = jax.default_backend() != "cpu"
        by_key: dict[tuple, list[int]] = {}
        for i, spec in enumerate(self.specs):
            by_key.setdefault(
                (spec.ladder, spec.receiver, spec.options), []
            ).append(i)
        self.groups: list[_LadderGroup] = []
        self._group_of: dict[int, _LadderGroup] = {}
        for (ladder, receiver, options), idxs in by_key.items():
            ladder_name, rungs = resolve_ladder(ladder)
            g = _LadderGroup(
                ladder_name, rungs, receiver, dict(options), idxs, donate
            )
            self.groups.append(g)
            for i in idxs:
                self._group_of[i] = g

        self.registry = registry if registry is not None else get_registry()
        self.exec_stats = ExecStats()
        self.slot_gen = SlotGenerator(registry=self.registry,
                                      stats=self.exec_stats)
        self._uid_bases: list[int] = []
        uid_base = 0
        for spec in self.specs:
            self._uid_bases.append(uid_base)
            uid_base += spec.n_users
        self.loops: list[CellLoop] = [
            self._make_loop(i) for i in range(len(self.specs))
        ]

        if mesh is None:
            mesh = make_cell_mesh(len(self.specs))
        self.mesh = mesh
        self._donate = donate
        # lane buckets must stay divisible by the mesh's cell axis so
        # every staged step shards evenly over the mesh
        self._min_lanes = int(self.mesh.devices.shape[0])
        self.bucket_policy = (
            bucket_policy if bucket_policy is not None
            else PowerOfTwoBuckets(self._min_lanes)
        )
        max_lanes = max(len(g.cell_idxs) for g in self.groups)
        for b in self.bucket_policy.buckets(max_lanes):
            if b % self._min_lanes:
                raise ValueError(
                    f"bucket {b} of {self.bucket_policy!r} is not a "
                    f"multiple of the mesh cell axis ({self._min_lanes})"
                )
        self.tick_times: list[float] = []
        self.wall_s = 0.0
        self.n_steps = 0
        self.n_filler_lanes = 0
        self.n_real_lanes = 0
        self.now = 0
        if prebuild:
            self._prebuild()

    @classmethod
    def uniform(cls, ladder: str, n_cells: int, *, n_users: int = 4,
                arrival_rate: float = 1.0, snr_db: Optional[float] = None,
                snr_spread_db: float = 0.0, init_mcs: int = 0,
                receiver: str = "classical", hot_cells: int = 0,
                hot_factor: float = 1.0, tx_power_db: float = 0.0,
                coupling_db: Optional[float] = None,
                options: Optional[dict] = None,
                **kw) -> "MeshSlotScheduler":
        """N same-config cells; the first ``hot_cells`` get their arrival
        rate multiplied by ``hot_factor`` (load-skew sweeps).  Setting
        ``coupling_db`` couples every cell to its N-1 siblings (see
        :class:`ClosedCellSpec`)."""
        specs = [
            closed_cell(
                f"cell{i}", ladder, receiver, n_users=n_users,
                arrival_rate=(arrival_rate * hot_factor if i < hot_cells
                              else arrival_rate),
                snr_db=snr_db, snr_spread_db=snr_spread_db,
                init_mcs=init_mcs, tx_power_db=tx_power_db,
                coupling_db=coupling_db, **(options or {}),
            )
            for i in range(n_cells)
        ]
        return cls(specs, **kw)

    def _make_loop(self, i: int) -> CellLoop:
        """Build cell ``i``'s :class:`CellLoop` from its spec.

        Factored out of ``__init__`` so a supervisor can reconstruct a
        crashed cell (same spec, same seeded RNG stream — the restored
        checkpoint then overwrites the stream position and state).
        """
        spec = self.specs[i]
        g = self._group_of[i]
        return CellLoop(
            g.rungs, name=spec.name, rng=cell_rng(self.seed, i),
            n_users=spec.n_users, batch_size=self.batch_size,
            arrival_rate=spec.arrival_rate, max_retx=self.max_retx,
            deadline_ttis=self.deadline_ttis,
            max_batches_per_tick=self.max_batches_per_tick,
            adapt=self.adapt, target_bler=self.target_bler,
            olla_step=self.olla_step, init_mcs=spec.init_mcs,
            snr_db=spec.snr_db, snr_spread_db=spec.snr_spread_db,
            interferer_db=self._coupled_interferers(i),
            uid_base=self._uid_bases[i], job_ids=self.job_counter,
            slot_gen=self.slot_gen,
        )

    def _coupled_interferers(self, i: int) -> tuple:
        """Cell ``i``'s co-channel interferer powers from its same-group
        neighbors: ``sibling.tx_power_db + coupling_db`` for every other
        cell in the ladder group (dB relative to the served signal).
        ``coupling_db=None`` (the default) decouples the cell entirely —
        a 1-cell mesh or an uncoupled N-cell mesh replays byte-identical
        to the matching single-cell :class:`SlotScheduler` run.
        """
        spec = self.specs[i]
        if spec.coupling_db is None:
            return ()
        return tuple(
            self.specs[j].tx_power_db + spec.coupling_db
            for j in self._group_of[i].cell_idxs
            if j != i
        )

    # -- invariants (the test harness's observation surface) --------------
    @property
    def jobs_submitted(self) -> int:
        return self.job_counter.n

    def finalized_job_ids(self) -> list[int]:
        return [j for loop in self.loops for j in loop.finalized_jobs]

    def queued_job_ids(self) -> list[int]:
        return [
            j.job_id
            for loop in self.loops
            for u in loop.users
            for j in u.backlog
        ]

    @property
    def harq_open(self) -> int:
        return sum(loop.harq_open for loop in self.loops)

    @property
    def backlog(self) -> int:
        return sum(loop.backlog for loop in self.loops)

    def inject_backlog(self, n_per_user: int) -> None:
        for loop in self.loops:
            loop.inject_backlog(n_per_user)

    # -- rebalancing: inter-cell handover + load shedding -----------------
    def _rebalance(self) -> None:
        """Migrate users off saturated cells; shed as the last resort.

        A cell saturates when its pending jobs exceed
        :meth:`CellLoop.capacity_jobs` — the most it can serve inside the
        deadline budget at its pool capacity (unlimited pools never
        saturate, so this is a no-op unless ``max_batches_per_tick`` is
        set).  Users move whole (queue + HARQ state + OLLA state) to the
        least-loaded same-group sibling, and only when the move fits the
        receiver's headroom — otherwise overload would just slosh.
        """
        for g in self.groups:
            loops = [self.loops[i] for i in g.cell_idxs]
            for donor in loops:
                while donor.pending_jobs() > donor.capacity_jobs():
                    moved = False
                    recvs = [
                        l for l in loops
                        if l is not donor
                        and l.pending_jobs() < l.capacity_jobs()
                    ]
                    movable = [u for u in donor.users if u.backlog]
                    if recvs and movable and len(donor.users) > 1:
                        recv = min(recvs, key=lambda l: l.pending_jobs())
                        user = max(movable, key=lambda u: len(u.backlog))
                        headroom = (recv.capacity_jobs()
                                    - recv.pending_jobs())
                        moved_load = len(user.backlog)
                        # migrate when the receiver absorbs the load
                        # inside its budget, or when the move strictly
                        # improves balance (no overload sloshing)
                        if moved_load <= headroom or (
                            recv.pending_jobs() + moved_load
                            < donor.pending_jobs()
                        ):
                            donor.users.remove(user)
                            recv.users.append(user)
                            donor.handover_out += 1
                            recv.handover_in += 1
                            moved = True
                    if not moved:
                        overflow = int(
                            donor.pending_jobs() - donor.capacity_jobs()
                        )
                        donor.shed_tail(overflow)
                        break  # HARQ-active jobs may keep it over cap

    # -- staging ----------------------------------------------------------
    def _bucket(self, n_lanes: int) -> int:
        """The registered lane bucket a dynamic lane count maps onto —
        delegated to the pluggable :class:`BucketPolicy`."""
        return self.bucket_policy.bucket_for(n_lanes)

    def _stage(self, lanes: list[_ClosedLane],
               bucket: Optional[int] = None) -> dict:
        """Stack one step's lanes to sharded (n_lanes, batch, ...) arrays,
        padding with filler lanes (replaying lane 0) to the policy's lane
        bucket."""
        if bucket is None:
            bucket = self._bucket(len(lanes))
        with span("serve.stage", lanes=len(lanes), bucket=bucket):
            per_lane = [
                stack_slots(lane.slots, lane.pad, xp=np) for lane in lanes
            ]
            per_lane += [per_lane[0]] * (bucket - len(lanes))
            stacked = {
                k: np.stack([np.asarray(pl[k]) for pl in per_lane], axis=0)
                for k in per_lane[0]
            }
            shardings = shd.cell_slot_shardings(
                stacked, self.mesh, batched_keys=BATCHED_KEYS
            )
            return {
                k: jax.device_put(v, shardings[k])
                for k, v in stacked.items()
            }

    # -- the lockstep TTI loop --------------------------------------------
    #
    # tick() is decomposed into overridable hooks so a supervisor
    # (repro.serve.supervisor) can interpose fault handling without
    # duplicating the lockstep machinery.  The base implementations keep
    # semantics bit-identical to the pre-hook monolithic loop.

    def _begin_tick(self) -> None:
        """Hook before any per-tick mutation (supervisor: crash/restore,
        quarantine lifecycle).  Base: no-op."""

    def _cell_plannable(self, ci: int) -> bool:
        """Whether cell ``ci`` may plan batches this tick (supervisor:
        False while quarantined — arrivals still accrue).  Base: True."""
        return True

    def _plan_tick(self) -> list:
        """Plan every cell's batches, bucketed per (ladder group, rung)."""
        work: dict[tuple, list[_ClosedLane]] = {}
        with span("serve.plan") as sp:
            for gi, g in enumerate(self.groups):
                for ci in g.cell_idxs:
                    if not self._cell_plannable(ci):
                        continue
                    loop = self.loops[ci]
                    for mcs, pairs in loop.plan_batches():
                        slots = [
                            loop.make_slot(u, job, mcs) for u, job in pairs
                        ]
                        loop.n_batches += 1
                        work.setdefault((gi, mcs), []).append(_ClosedLane(
                            cell_idx=ci, pairs=pairs, slots=slots,
                            pad=self.batch_size - len(pairs),
                        ))
            sp.set_metadata(batches=sum(len(v) for v in work.values()))
        return sorted(work.items())

    def _serve_items(self, items: list, stats: list[TickStats]) -> None:
        """Serve the tick's buckets; staging of bucket k+1 overlaps device
        compute of bucket k (the prefetch thunk runs inside _dispatch's
        async-dispatch window), warmups are untimed."""
        if not items:
            return
        staged = self._stage(items[0][1])
        for i, ((gi, mcs), lanes) in enumerate(items):
            prefetch = (
                (lambda j=i + 1: self._stage(items[j][1]))
                if i + 1 < len(items) else None
            )
            staged = self._dispatch(gi, mcs, lanes, staged, stats,
                                    prefetch)

    def _dispatch(self, gi: int, mcs: int, lanes: list[_ClosedLane],
                  staged: dict, stats: list[TickStats],
                  prefetch=None) -> Optional[dict]:
        """Run one (group, rung) bucket step and fan feedback back out.

        Returns the next bucket's staged batch (from ``prefetch``), so
        the caller's double buffering survives overrides.
        """
        bucket = self._bucket(len(lanes))
        step = self._step_for(gi, mcs, bucket, staged)
        with step_window(self, lanes=len(lanes), bucket=bucket, mcs=mcs,
                         **shape_counts(self.groups[gi].rungs[mcs])):
            state = step(staged)  # async dispatch
            nxt = prefetch() if prefetch is not None else None
            state = wait(state)
        self.n_steps += 1
        self.n_real_lanes += len(lanes)
        self.n_filler_lanes += bucket - len(lanes)
        self._feedback(lanes, mcs, state, stats)
        return nxt

    def _step_for(self, gi: int, mcs: int, bucket: int, example: dict):
        """Acquire the (group, rung, bucket, schema) AOT step from the
        registry.  Resident steps are a dict lookup; cold ones compile —
        or load from the persistent cache — *before* the timed window,
        which is why first-tick latency no longer hides compile stalls.
        Acquisition never executes, so donated example buffers survive."""
        g = self.groups[gi]
        key = (mcs, bucket, slot_schema(example))
        step = g._execs.get(key)
        if step is None:
            step = self.registry.acquire_pipeline_step(
                g.pipelines[mcs], example, batch=self.batch_size,
                lanes=bucket, donate=g.donate, stats=self.exec_stats,
            )
            g._execs[key] = step
        return step

    def _prebuild(self) -> None:
        """AOT-populate every (group, rung) step at the group's base lane
        bucket, and every cell's slot-generator executables, before the
        first TTI.  Templates ride the exact staging
        path dispatch uses; with a warm persistent cache this is all
        cache hits, so a fresh process reaches its first served TTI with
        zero new XLA compilations.  Buckets beyond the base (bursty
        ticks) acquire lazily — still through the registry, so they
        persist for the next process too."""
        from repro.phy.scenarios import get_scenario, ladder_exec_specs

        for gi, g in enumerate(self.groups):
            bucket = self._bucket(len(g.cell_idxs))
            specs = ladder_exec_specs(
                g.ladder_name, receiver=g.receiver,
                batch=self.batch_size, lane_buckets=(bucket,), harq=True,
            )
            for mcs, spec in enumerate(specs):
                lane = _ClosedLane(
                    cell_idx=None,
                    slots=[template_slot(
                        get_scenario(spec.scenario), harq=spec.harq
                    )],
                    pad=self.batch_size - 1,
                )
                staged = self._stage([lane], bucket=spec.lanes)
                self._step_for(gi, mcs, spec.lanes, staged)
        for loop in self.loops:
            loop.prebuild_slots()

    def _end_tick_hook(self, stats: list[TickStats]) -> None:
        """Hook after every cell's end_tick (supervisor: periodic
        checkpointing).  Base: no-op."""

    def tick(self) -> list[TickStats]:
        """Advance every cell one TTI in lockstep."""
        with span("serve.tick") as sp:
            self._begin_tick()
            stats = [TickStats(tick=loop.now) for loop in self.loops]
            with span("serve.arrive"):
                for loop, st in zip(self.loops, stats):
                    loop.arrive(st)
            with span("serve.rebalance"):
                self._rebalance()
            items = self._plan_tick()
            sp.set_metadata(slots=sum(
                len(lane.pairs) for _, lanes in items for lane in lanes))
            n0, w0 = self.n_steps, self.wall_s
            self._serve_items(items, stats)
            # first vs steady-state latency: only ticks that served a step
            if self.n_steps > n0:
                self.tick_times.append(self.wall_s - w0)
            with span("serve.end_tick"):
                for loop, st in zip(self.loops, stats):
                    loop.end_tick(st)
                self._end_tick_hook(stats)
            self.now += 1
        return stats

    def _feedback(self, lanes: list[_ClosedLane], mcs: int, state: dict,
                  stats: list[TickStats]) -> None:
        with span("serve.feedback", lanes=len(lanes)):
            crc_ok = np.asarray(state["crc_ok"])  # (L, B, C)
            cw_llr = np.asarray(state["cw_llr"])  # (L, B, C, n_mother)
            for li, lane in enumerate(lanes):
                loop = self.loops[lane.cell_idx]
                for j, (u, job) in enumerate(lane.pairs):
                    loop.serve_feedback(
                        u, job, mcs, crc_ok[li, j].astype(bool),
                        cw_llr[li, j : j + 1], stats[lane.cell_idx],
                    )

    def run(self, n_ticks: int) -> MeshClosedLoopReport:
        for _ in range(n_ticks):
            self.tick()
        return self.report()

    # -- reporting --------------------------------------------------------
    def report(self) -> MeshClosedLoopReport:
        cells = {}
        for i, loop in enumerate(self.loops):
            g = self._group_of[i]
            cells[loop.name] = loop.report(
                ladder_name=g.ladder_name, receiver=g.receiver,
                pipelines=g.pipelines, wall_s=self.wall_s,
                n_batches=loop.n_batches,
            )
        loops = self.loops
        wall_safe = max(self.wall_s, 1e-9)
        served = sum(l._served for l in loops)
        missed = sum(l._missed for l in loops)
        ftx_blocks = sum(l._first_tx_blocks for l in loops)
        ftx_errors = sum(l._first_tx_errors for l in loops)
        delivered = sum(sum(l._delivered) for l in loops)
        lost = sum(l._lost for l in loops)
        rounds = [r for l in loops for r in l._rounds]
        good_bits = sum(l.good_bits() for l in loops)
        # occupancy-weighted energy over every (group, rung) pipeline
        occ, pipes = [], []
        for g in self.groups:
            for r in range(len(g.rungs)):
                occ.append(sum(
                    self.loops[i]._occupancy[r] for i in g.cell_idxs
                ))
                pipes.append(g.pipelines[r])
        energy, gops_w, l1_res = occupancy_energy(occ, pipes)
        first_s, steady_s = first_steady(self.tick_times)
        return MeshClosedLoopReport(
            n_cells=len(self.loops),
            n_groups=len(self.groups),
            mesh_shape=tuple(self.mesh.devices.shape),
            batch_size=self.batch_size,
            n_users=sum(len(l.users) for l in loops),
            n_ticks=self.now,
            max_retx=self.max_retx,
            n_slots=served,
            n_steps=self.n_steps,
            n_filler_lanes=self.n_filler_lanes,
            wall_s=self.wall_s,
            slots_per_sec=served / wall_safe,
            n_arrivals=sum(l._arrivals for l in loops),
            deadline_miss_rate=missed / served if served else 0.0,
            first_tx_bler=(
                ftx_errors / ftx_blocks if ftx_blocks else None
            ),
            residual_bler=(
                lost / (lost + delivered) if lost + delivered else None
            ),
            mean_harq_rounds=(
                float(np.mean(rounds)) if rounds else None
            ),
            blocks_delivered=delivered,
            blocks_lost=lost,
            jobs_shed=sum(l.jobs_shed for l in loops),
            handovers=sum(l.handover_in for l in loops),
            goodput_bits_per_sec=good_bits / wall_safe,
            goodput_bits_per_tti=good_bits / max(self.now, 1),
            backlog_left=self.backlog,
            harq_open=self.harq_open,
            precision=self.groups[0].pipelines[0].precision,
            energy_uj_per_slot=energy,
            gops_per_watt=gops_w,
            l1_residency=l1_res,
            compile_time_s=self.exec_stats.compile_time_s,
            executables_compiled=self.exec_stats.executables_compiled,
            cache_hits=self.exec_stats.cache_hits,
            first_tick_s=first_s,
            steady_tick_s=steady_s,
            cells=cells,
        )
