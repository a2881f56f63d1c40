"""Summed device time of the ``ldpc_decode`` kernel over slots served."""
from metrics.kernel_time import us_per_slot


def read(run):
    return us_per_slot(run, "ldpc_decode")
