"""Summed device time of the fused SIC detect+demap kernel
(``rx_sic_demap``) over slots served."""
from metrics.kernel_time import us_per_slot


def read(run):
    return us_per_slot(run, "rx_sic_demap")
