"""Receiver operations of the slots served in the window (FFT, LS-CHE,
Wiener smoothing, detection and demap, LDPC at one iteration per
codeword: a lower bound) over the window times the chip's bf16 peak."""
import ops


def read(run):
    w = run.window
    if w.window_s <= 0 or not w.slots:
        return None
    work = sum(n * ops.per_slot(run.rungs[name], run.fused)
               for name, n in w.slots_by_rung.items())
    return 100.0 * work / (w.window_s * run.n_chips * run.peaks["bf16_flops"])
