"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for.  It sets up the cell (compiles, or loads from the compile cache
in ``.cache/bench-jax`` of the checkout), warms up, serves for
``--seconds``, compares a sample of the window's answers with the plain
reference, and prints one JSON object as the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the compile cache lives at a fixed path inside the checkout; the
    # program's registry follows the same variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".cache", "bench-jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import harness

    _, cell, _ = harness.cell_spec(args.workload)
    devices, kind = harness.guard_devices(cell["chips"])
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), devices, T_PROCESS, kind)
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
