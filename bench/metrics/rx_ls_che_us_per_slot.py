"""Device time of the fused LS channel-estimation kernel (``rx_ls_che``)
over slots served.

Only the kernel's own operations count: the trace's text of an
operation that reads the kernel's output names it too (the Wiener
smoother's fusion does), so the name is matched against each
operation's kind and not searched for in its text."""
import xtrace

KERNEL = "rx_ls_che"


def kernel_s(trace) -> float:
    """Device seconds of the kernel's operations, summed over devices."""
    return sum(o.end - o.start for o in trace.ops
               if xtrace.short_name(o.name) == KERNEL) * 1e-9


def read(run):
    w = run.window
    if w.trace is None or not w.slots:
        return None
    t = kernel_s(w.trace)
    return t / w.slots * 1e6 if t > 0 else None
