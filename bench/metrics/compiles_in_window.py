"""Executables XLA built, or loaded from the compile cache, inside the
measured window (should be 0)."""


def read(run):
    return float(run.window.compiles)
