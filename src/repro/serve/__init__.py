"""Serving engines: LM request batching (:class:`ServeEngine`), single-cell
PHY slot serving (:class:`PhyServeEngine`), multi-cell sharded PHY serving
over a (cell, batch) device mesh (:class:`CellMeshEngine`), and the
closed-loop TTI runtime with HARQ + link adaptation — single cell
(:class:`SlotScheduler`) and mesh scale (:class:`MeshSlotScheduler`).
The PHY paths share one slot-scheduler core (:mod:`repro.serve.runtime`),
and the closed-loop paths share one per-cell state machine
(:class:`CellLoop`).  Fault tolerance rides on top: deterministic fault
injection (:class:`FaultPlan`/:class:`FaultInjector`) and the supervised
runtime (:class:`Supervisor`, :class:`SupervisedBatchRunner`) with
non-finite guards, bounded retries, cell quarantine, and checkpointed
crash recovery.

Every compiled serving step is owned by the process-wide AOT executable
registry (:mod:`repro.serve.exec_registry`): keyed by (scenario, receiver,
precision, batch bucket, backend), populated ahead of the first TTI,
backed by a persistent on-disk compilation cache
(``$JAX_COMPILATION_CACHE_DIR``, else ``.cache/jax`` in the checkout),
with pluggable batch-bucketing policies (:class:`PowerOfTwoBuckets`,
:class:`FixedBuckets`, :class:`CostModelBuckets`)."""
from repro.serve.engine import ServeEngine, Request
from repro.serve.exec_registry import (
    BucketPolicy, CostModelBuckets, ExecKey, ExecRegistry, ExecStats,
    FixedBuckets, PowerOfTwoBuckets, default_cache_dir,
    disable_persistent_cache, enable_persistent_cache, exec_key_for,
    get_registry, set_registry,
    slot_schema, template_batch, template_slot,
)
from repro.serve.runtime import (
    BatchRunner, CellLoop, ClosedLoopReport, JobCounter, PhyServeReport,
    SlotLedger, SlotRequest, SlotScheduler, build_serve_report, cell_rng,
    make_traffic, rng_key, slot_metric_means, stack_slots, validate_slots,
)
from repro.serve.phy_engine import PhyServeEngine
from repro.serve.cell_mesh import (
    CellMeshEngine, CellSpec, ClosedCellSpec, MeshClosedLoopReport,
    MeshServeReport, MeshSlotScheduler, cell, closed_cell,
)
from repro.serve.faults import (
    FAULT_KINDS, FaultEvent, FaultInjector, FaultPlan, InjectedFault,
)
from repro.serve.supervisor import (
    SupervisedBatchRunner, Supervisor, restore_cell_loop,
    snapshot_cell_loop,
)
