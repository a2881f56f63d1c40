"""Batched PHY slot-serving engine (open-loop, single cell).

A thin frontend over the shared slot-scheduler core in
:mod:`repro.serve.runtime`: submit bookkeeping rides on
:class:`~repro.serve.runtime.SlotLedger`, batching/padding and the timed
execution loop on :class:`~repro.serve.runtime.BatchRunner`, and the
report on :func:`~repro.serve.runtime.build_serve_report` — the same
pieces the multi-cell mesh engine and the closed-loop
:class:`~repro.serve.runtime.SlotScheduler` use, so all serving paths
batch, time, and score slots identically.

This engine drains a pre-filled queue once (open loop, no feedback); for
TTI-clocked closed-loop serving with HARQ and link adaptation see
:class:`repro.serve.runtime.SlotScheduler`.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.phy import link as _link
from repro.serve.runtime import (  # noqa: F401  (re-exported API)
    BATCHED_KEYS,
    BatchRunner,
    PhyServeReport,
    SlotLedger,
    SlotRequest,
    build_serve_report,
    make_traffic,
)
from repro.serve.trace import span


class PhyServeEngine:
    """Drain a queue of per-user slots through one ReceiverPipeline.

    All batches have the same static shape (the last one is padded by
    repeating its first user), so the pipeline compiles exactly once.
    """

    def __init__(self, pipeline: _link.ReceiverPipeline, batch_size: int,
                 *, supervised: bool = False, receiver: str = "classical",
                 max_retries: int = 2, backoff_s: float = 0.0):
        self.pipeline = pipeline
        self.batch_size = batch_size
        # supervised serving guards every batch: bounded retry on step
        # exceptions, non-finite outputs degrade once to the fp32
        # unfused reference pipeline (repro.serve.supervisor)
        self.supervised = supervised
        self.receiver = receiver
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self._queue: list[SlotRequest] = []
        self._ledger = SlotLedger()

    @classmethod
    def from_scenario(cls, scenario, receiver: str = "classical",
                      batch_size: int = 4, supervised: bool = False,
                      **options) -> "PhyServeEngine":
        """Build the pipeline and the engine in one go.

        ``scenario`` is a registered name or a LinkScenario; ``options``
        pass through to the pipeline builder (e.g. ``fused=True`` to serve
        the classical chain through the fused receiver kernels).
        ``supervised=True`` serves through the guarded
        :class:`~repro.serve.supervisor.SupervisedBatchRunner`.
        """
        from repro.phy.scenarios import get_scenario

        if isinstance(scenario, str):
            scenario = get_scenario(scenario)
        return cls(
            _link.build_pipeline(receiver, scenario, **options),
            batch_size=batch_size, supervised=supervised,
            receiver=receiver,
        )

    def _make_runner(self) -> BatchRunner:
        if not self.supervised:
            return BatchRunner(self.pipeline, self.batch_size)
        # lazy import: supervisor imports the serving core, not vice versa
        from repro.serve.supervisor import SupervisedBatchRunner

        return SupervisedBatchRunner(
            self.pipeline, self.batch_size, receiver=self.receiver,
            max_retries=self.max_retries, backoff_s=self.backoff_s,
        )

    # -- traffic ----------------------------------------------------------
    def submit(self, slot: dict, user_id: Optional[int] = None
               ) -> SlotRequest:
        req = self._ledger.new_request(slot, user_id)
        self._queue.append(req)
        return req

    def submit_traffic(self, key: jax.Array, n_users: int
                       ) -> list[SlotRequest]:
        """Simulate ``n_users`` independent single-slot arrivals."""
        return [
            self.submit(slot)
            for slot in make_traffic(self.pipeline.scenario, key, n_users)
        ]

    # -- serving ----------------------------------------------------------
    def run(self, warmup: bool = True) -> PhyServeReport:
        """Serve every queued slot; returns the throughput/quality report.

        ``warmup=True`` acquires the AOT executable from the process
        :class:`~repro.serve.exec_registry.ExecRegistry` before the timed
        window opens (a registry/persistent-cache hit when already
        resident — no batch is executed twice), so the reported slots/sec
        measures the steady-state executable, not compilation.  Compile
        accounting and first/steady batch latency land on the report.
        """
        reqs = self._queue
        self._queue = []
        runner = self._make_runner()
        n_batches = runner.drain(reqs, warmup=warmup)
        with span("serve.report"):
            return build_serve_report(
                self.pipeline, self.pipeline.scenario,
                [r.metrics for r in reqs],
                n_slots=len(reqs), n_batches=n_batches,
                batch_size=self.batch_size, wall_s=runner.wall_s,
                exec_stats=runner.exec_stats,
                batch_times=runner.batch_times,
                filter_stats=runner.filter_stats,
            )
