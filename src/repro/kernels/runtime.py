"""Shared Pallas runtime helper for the kernel modules.

``resolve_interpret`` — kernels take ``interpret=None`` and auto-select:
interpreter mode everywhere except a real TPU backend, so the same call
sites validate on CPU and compile to Mosaic on the chip.  On a TPU backend
a kernel never runs interpreted unless its caller asks for it.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpret everywhere but TPU; bools pass through."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
