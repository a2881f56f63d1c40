"""Info bits of CRC-passing code blocks delivered in the window, over the
window's wall time, in Mbit/s."""


def read(run):
    w = run.window
    if w.window_s <= 0 or not w.slots:
        return None
    return w.info_bits_ok / w.window_s / 1e6
