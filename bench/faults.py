"""Faults planted under the timed path, to see that a broken run comes
out not correct.

Each breaks the program's served step, as a later change could: a step
that returns the previous step's state (``stale``), one whose second
half of the batch carries the first half's answers (``half``), and one
that flips the first code block's CRC outcome of every slot where it is
made (``altered``).  No cell exchanges data between chips, so there is
no exchange to leave out.
"""
from __future__ import annotations

import contextlib

FAULTS = ("stale", "half", "altered")


def corrupt(state: dict, fault: str, axis: int, prev: dict, key) -> dict:
    """``state`` of one step, broken by ``fault``; ``axis`` is the batch
    axis and ``prev`` holds the previous state of each step ``key``."""
    import jax.numpy as jnp

    if fault == "stale":
        out = prev.get(key, state)
        prev[key] = state
        return out
    state = dict(state)
    if fault == "half":
        b = state["crc_ok"].shape[axis]
        for k, v in state.items():
            if getattr(v, "ndim", 0) > axis and v.shape[axis] == b:
                head = jnp.take(v, jnp.arange(b // 2), axis=axis)
                state[k] = jnp.concatenate(
                    [head, jnp.take(v, jnp.arange(b - b // 2), axis=axis)],
                    axis=axis)
    else:  # "altered"
        crc = state["crc_ok"]
        state["crc_ok"] = crc.at[..., 0].set(~crc[..., 0])
    return state


@contextlib.contextmanager
def planted(kind: str, fault: str):
    """Break the served step of a ``kind`` driver with ``fault`` while the
    block runs: ``BatchRunner._execute`` for a backlog, the mesh step of
    ``MeshSlotScheduler._step_for`` (lanes on axis 0, batch on 1) for a
    closed loop."""
    from repro.serve.cell_mesh import MeshSlotScheduler
    from repro.serve.runtime import BatchRunner

    prev = {}
    if kind == "backlog":
        cls, name, orig = BatchRunner, "_execute", BatchRunner._execute

        def broken(self, batch):
            return corrupt(orig(self, batch), fault, 0, prev, "step")
    else:
        cls, name = MeshSlotScheduler, "_step_for"
        orig = MeshSlotScheduler._step_for

        def broken(self, gi, mcs, bucket, example):
            step = orig(self, gi, mcs, bucket, example)
            return lambda staged: corrupt(step(staged), fault, 1, prev,
                                          (gi, mcs))
    setattr(cls, name, broken)
    try:
        yield
    finally:
        setattr(cls, name, orig)
