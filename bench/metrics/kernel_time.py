"""Device time of one kernel per slot served, from the trace."""


def us_per_slot(run, kernel: str):
    w = run.window
    if w.trace is None or not w.slots:
        return None
    t = w.trace.kernel_s(kernel) * w.trace.n_devices
    return t / w.slots * 1e6 if t > 0 else None
