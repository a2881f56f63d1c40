"""Spans of the serving loop, on the JAX profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
being taken it lands on the trace's host plane, on the same clock as the
device's operations, with its keyword counts as the event's stats; when
none is, it is an inactive TraceMe (about a microsecond).  Whether spans
are recorded is up to whoever starts the profiler.

Every name starts with ``serve.``, so none can be taken for a span of the
code that drives the loop.  The spans and what they hold:

* ``serve.tick`` (``slots``): one ``MeshSlotScheduler.tick``, with its
  ``serve.arrive``, ``serve.rebalance``, ``serve.plan`` (``batches``)
  and ``serve.end_tick`` phases;
* ``serve.make_slot`` (``retx`` 0/1, ``compiled``): one user's slot,
  built by one call of a slot-generator executable (``compiled`` 1 when
  it was prebuilt, 0 when this call built it);
* ``serve.stage`` (``lanes``, ``bucket``): one mesh step's lanes stacked
  and put on the device;
* ``serve.dispatch`` (mesh: ``lanes``, ``bucket``, ``mcs``; with the
  step's :func:`shape_counts`): one served step's timed window, step
  call to the end of its ``serve.wait``;
* ``serve.wait``: the host blocked on a served step's outputs;
* ``serve.feedback`` (``lanes``): CRC results fanned back to the cells;
* ``serve.batch`` (``slots``, the real ones, and :func:`shape_counts`),
  with ``serve.stack`` and ``serve.slot_metrics``: one
  ``BatchRunner.run_batch``;
* ``serve.report``: a report built after ``PhyServeEngine.run``;
* ``serve.acquire`` (``compiled``, ``cache_hit``): an executable built,
  or loaded from the persistent cache, by the registry.
"""
from __future__ import annotations

import contextlib
import time

import jax


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A ``serve.*`` span carrying ``counts`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **counts)


def shape_counts(scenario) -> dict:
    """The width of a served step, as span stats: ``n_sc`` (subcarriers)
    and ``codewords`` (code blocks of one slot; 0 when uncoded)."""
    from repro.phy import coding

    cw = coding.codewords_per_slot(scenario) if scenario.code else 0
    return {"n_sc": scenario.grid.n_subcarriers, "codewords": cw}


def wait(x):
    """``jax.block_until_ready(x)`` under a ``serve.wait`` span."""
    with span("serve.wait"):
        return jax.block_until_ready(x)


@contextlib.contextmanager
def step_window(owner, **counts):
    """One served step's timed window under a ``serve.dispatch`` span.

    The window's wall time is added to ``owner.wall_s``, also when the
    step raises; the yielded dict holds it under ``"dt"`` once the block
    is left.
    """
    out = {}
    t0 = time.perf_counter()
    try:
        with span("serve.dispatch", **counts):
            yield out
    finally:
        out["dt"] = time.perf_counter() - t0
        owner.wall_s += out["dt"]
