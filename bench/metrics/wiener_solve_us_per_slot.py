"""Device time of the Wiener smoother's linear solve over slots served:
the operations whose trace text names ``LuDecomposition`` (the LU
factorization's blocks) or ``InvertDiagBlocks`` (the triangular solves'
diagonal-block inverses)."""

NAMES = ("LuDecomposition", "InvertDiagBlocks")


def read(run):
    w = run.window
    if w.trace is None or not w.slots:
        return None
    t = sum(o.end - o.start for o in w.trace.ops
            if any(k in o.text for k in NAMES)) * 1e-9
    return t / w.slots * 1e6 if t > 0 else None
