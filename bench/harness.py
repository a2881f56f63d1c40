"""One run of one cell: set-up, measured window, check, result line.

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic mix (``traffic/<mix>.json``) and one
reader per metric (``metrics/<metric>.py``).
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import types


BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS_MAX = 10.0  # a traced window is shorter: traces are large


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str) -> tuple:
    """(benchmark, cell, configuration entry) for a workload name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, conf


def metric_names(bench: dict, cell: dict, trace: bool) -> list:
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader(name: str):
    return importlib.import_module(f"metrics.{name}").read


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r}; "
                         f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             t_process: float, device_kind: str) -> dict:
    """One run of the workload ``name`` of ``BENCHMARK.json``."""
    bench, cell, conf = cell_spec(name)
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return measure(metric_names(bench, cell, trace), config, traffic, seed,
                   seconds, trace, devices, t_process, device_kind)


def measure(metrics: list, config: dict, traffic: dict, seed: int,
            seconds: float, trace: bool, devices, t_process: float,
            device_kind: str, control: bool = False, counter=None) -> dict:
    """Set up, measure, check.  Returns the result line's object; with
    ``control`` it also holds the precision control's readings."""
    import time

    import jax

    import drivers
    import phy

    counter = counter or drivers.CompileCounter()
    kind = drivers.KINDS[traffic["kind"]]
    t_init = time.perf_counter()
    drv = kind(config, traffic, seed, devices, counter)
    t_ready = time.perf_counter()
    setup_s = t_ready - t_process
    print(f"setup {setup_s:.3f} s: start-up and JAX init "
          f"{t_init - t_process:.3f} s, build {drv.t_build - t_init:.3f} s, "
          f"warm-up {t_ready - drv.t_build:.3f} s; {counter.n} executables, "
          f"{counter.hits} of them from the compile cache",
          file=sys.stderr, flush=True)
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(log_dir)
        seconds = min(seconds, TRACE_SECONDS_MAX)
    try:
        w = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    if trace:
        import xtrace

        w.trace = xtrace.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    # the reference runs once the program's state is freed
    drv.release()
    gc.collect()
    got, ctl = drv.readings(control)
    print("readings " + json.dumps(got), file=sys.stderr)
    limits = config["correct"]
    compared = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = (all(v["value"] is not None and v["value"] <= v["limit"]
                   for v in compared.values())
               and drv.conservation_ok and w.slots > 0
               and got["n_slots"] > 0)

    run = types.SimpleNamespace(
        window=w, setup_s=setup_s, config=config,
        rungs={r.name: r for r in phy.rungs(config)},
        fused=config["receiver"]["fused"], peaks=peaks(device_kind),
        n_chips=len(devices))
    values = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": int(w.slots),
           "failed": 0 if drv.conservation_ok else int(w.slots),
           "metrics": values, "device": device}
    if trace:
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        out["breakdown"] = {"device_ops": w.trace.top_ops(),
                            "idle_gaps": w.trace.top_idle()}
    out["readings"] = got
    if control:
        out["control"] = ctl
    out["compared"] = {
        **compared,
        "conservation": {"value": int(not drv.conservation_ok),
                         "limit": 0},
        "sampled_slots": {"value": got["n_slots"], "limit": "> 0"},
    }
    return out


def report(out: dict) -> None:
    """Compared numbers beside their limits, last on standard error;
    the result object as the last line of standard output."""
    for k, v in out["compared"].items():
        print(f"compared {k}: {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def guard_devices(chips: int):
    """The first ``chips`` TPU chips, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found platform {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"the cell asks for {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips], devs[0].device_kind

