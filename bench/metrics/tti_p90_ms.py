"""90th percentile over every TTI of the window of the wall time of
``MeshSlotScheduler.tick()``: arrivals, rebalance, plan and slot
generation, staging, steps, HARQ/OLLA feedback."""
import numpy as np


def read(run):
    t = run.window.tick_s
    return float(np.percentile(t, 90)) * 1e3 if t else None
