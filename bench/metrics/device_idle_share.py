"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(run):
    tr = run.window.trace
    if tr is None or tr.window_s <= 0 or not tr.n_devices:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
