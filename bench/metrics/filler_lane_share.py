"""Share of staged mesh lanes that were filler (replayed, discarded)."""


def read(run):
    w = run.window
    if w.kind != "closed_loop" or not w.lanes_staged:
        return None
    return 100.0 * w.filler_lanes / w.lanes_staged
