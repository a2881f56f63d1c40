"""Share of its roofline the ``rx_ls_che`` kernel reached: the least time
the slots served need for their LS estimates on this chip
(``che_roofline``: operations over the bf16 peak or bytes over the HBM
bandwidth, whichever is larger) over the kernel's device time."""
import che_roofline
from metrics.rx_ls_che_us_per_slot import kernel_s


def read(run):
    w = run.window
    if w.trace is None or not w.slots:
        return None
    t = kernel_s(w.trace)
    if t <= 0:
        return None
    need = sum(n * che_roofline.roofline_s_per_slot(run.rungs[name],
                                                    run.peaks)
               for name, n in w.slots_by_rung.items())
    return 100.0 * need / t
