"""Set-up time: process start to the first timed TTI or batch (JAX
start-up, registry loads, pool generation, warm-up)."""


def read(run):
    return run.setup_s
