"""Host time per TTI: tick wall time minus the scheduler's dispatch
window (step call to ``block_until_ready``), mean over the window."""
import numpy as np


def read(run):
    w = run.window
    if not w.tick_s:
        return None
    return float(np.mean(np.subtract(w.tick_s, w.dispatch_s))) * 1e3
