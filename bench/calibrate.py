"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--faults stale,half,altered]

For each seed, in one process, one run of the cell as ``run.py`` makes it
(at ``--seconds``), whose sample is compared with the float32 reference
twice: the program's answers (the lower reading) and the control's, the
reference itself computed in bfloat16 and put in the program's place (the
upper reading).  Then, on the first seed, one run per planted fault
(``faults.py``).  Prints one JSON line per run.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seconds", type=float, default=10.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".cache", "bench-jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import drivers
    import faults
    import harness

    bench, cell, conf = harness.cell_spec(args.workload)
    devices, kind = harness.guard_devices(cell["chips"])
    config = harness.load_json(os.path.join(ROOT, conf["file"]))
    traffic = harness.load_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    counter = drivers.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = [(s, None) for s in seeds] + [
        (seeds[0], f) for f in args.faults.split(",") if f]
    for seed, fault in runs:
        t0 = time.perf_counter()
        with (faults.planted(traffic["kind"], fault) if fault
              else contextlib.nullcontext()):
            out = harness.measure(
                harness.metric_names(bench, cell, False), config, traffic,
                seed, args.fault_seconds if fault else args.seconds, False,
                devices, t0, kind, control=fault is None, counter=counter)
        print(json.dumps({
            "workload": cell["name"], "seed": seed, "fault": fault,
            "slots": out["attempted"], "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "program": out["readings"], "control": out.get("control"),
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "run_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
