"""Served slots over the window's wall time."""


def read(run):
    w = run.window
    return w.slots / w.window_s if w.window_s > 0 and w.slots else None
