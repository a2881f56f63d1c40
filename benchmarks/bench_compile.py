"""Cold-start benchmark: the AOT executable registry + persistent cache.

TensorPool's serving story assumes executables are resident before the
first TTI fires; the registry (:mod:`repro.serve.exec_registry`) makes
that true within a process, and its persistent on-disk XLA cache makes it
cheap across processes.  This bench measures exactly that boundary:

* **cold vs warm time-to-first-TTI** — the same small
  ``MeshSlotScheduler`` workload runs in two *fresh subprocesses*
  sharing one ``JAX_COMPILATION_CACHE_DIR`` (the fixed directory
  ``.cache/bench-compile-xla`` in the checkout, emptied at the start of
  each run).  The first (cold) process
  compiles every step; the second (warm) process must reach its first
  served TTI with **zero new XLA compilations** (``executables_compiled
  == 0``, ``cache_hits`` == executables needed) and a measurably smaller
  time-to-first-TTI.
* **steady-state parity** — an AOT ``Compiled`` step acquired from the
  registry must not serve slower than the plain ``jax.jit`` dispatch
  path the engines used before the registry existed (generous tolerance;
  the executable underneath is identical).

Standalone runs write ``experiments/phy/compile.json``, from which
``scripts/make_experiments_md.py`` regenerates docs/EXPERIMENTS.md.

Flags:
  --smoke   the two-process cold/warm gate + steady-state parity with
            one fewer tick — the CI cold-start gate; writes no JSON.
  --child   internal: run the child workload and print its stats JSON
            (spawned by the parent with ``JAX_COMPILATION_CACHE_DIR``
            pointed at the shared cache dir).
"""
import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

from benchmarks.common import emit, emit_json

JSON_PATH = "experiments/phy/compile.json"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".cache", "bench-compile-xla")
CHILD_MARK = "COMPILE_CHILD_JSON "
N_CELLS = 2
N_TICKS = 4
BATCH = 4
MICRO_REPS = 15
# the Compiled call path may not be slower than jit dispatch beyond
# python-overhead noise (same executable underneath)
PARITY_FACTOR = 1.3
PARITY_SLACK_S = 2e-3
WARM_TTF_FACTOR = 0.8


def _child_workload() -> dict:
    """One fresh-process serving run; returns timing + compile stats."""
    t0 = time.perf_counter()
    from benchmarks import bench_mesh_closed_loop as mcl
    from repro.phy.scenarios import get_ladder
    from repro.serve import MeshSlotScheduler

    ladder = mcl._ladder()
    rung0 = get_ladder(ladder).scenarios()[0]
    sch = MeshSlotScheduler.uniform(
        ladder, N_CELLS, n_users=2, arrival_rate=0.8,
        snr_db=rung0.snr_db + mcl.SNR_OFF, batch_size=BATCH,
        max_retx=2, adapt=False, seed=13,
    )
    ttf = None
    for _ in range(N_TICKS):
        sch.tick()
        if ttf is None and sch.tick_times:
            ttf = time.perf_counter() - t0  # first *served* TTI
    rep = sch.report()
    return {
        "time_to_first_tti_s": ttf,
        "executables_compiled": rep.executables_compiled,
        "cache_hits": rep.cache_hits,
        "compile_time_s": rep.compile_time_s,
        "first_tick_s": rep.first_tick_s,
        "steady_tick_s": rep.steady_tick_s,
        "slots_per_sec": rep.slots_per_sec,
        "n_slots": rep.n_slots,
    }


def _spawn_child(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_compile", "--child"],
        capture_output=True, text=True, env=env, cwd=ROOT, check=True,
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith(CHILD_MARK):
            return json.loads(line[len(CHILD_MARK):])
    raise RuntimeError(f"child emitted no stats:\n{out.stdout}\n{out.stderr}")


def bench_cold_warm() -> dict:
    """Cold then warm fresh-process runs over one shared cache dir."""
    shutil.rmtree(CACHE_DIR, ignore_errors=True)  # the cold run starts empty
    cold = _spawn_child(CACHE_DIR)
    warm = _spawn_child(CACHE_DIR)
    needed = cold["executables_compiled"] + cold["cache_hits"]
    emit("compile/cold_ttf", cold["time_to_first_tti_s"] * 1e6,
         f"compiled={cold['executables_compiled']} "
         f"hits={cold['cache_hits']}")
    emit("compile/warm_ttf", warm["time_to_first_tti_s"] * 1e6,
         f"compiled={warm['executables_compiled']} "
         f"hits={warm['cache_hits']}")

    # gate (a): the warm restart recompiles nothing and starts faster
    assert warm["executables_compiled"] == 0, warm
    assert warm["cache_hits"] == needed, (warm, needed)
    assert (warm["time_to_first_tti_s"]
            < WARM_TTF_FACTOR * cold["time_to_first_tti_s"]), (cold, warm)
    return {"cold": cold, "warm": warm, "executables_needed": needed}


def bench_steady_parity(reps: int = MICRO_REPS) -> dict:
    """Registry ``Compiled`` step vs plain ``jax.jit`` dispatch."""
    import jax

    from benchmarks import bench_mesh_closed_loop as mcl
    from repro.phy import link as _link
    from repro.phy.scenarios import get_ladder
    from repro.serve import get_registry, template_batch

    scn = get_ladder(mcl._ladder()).scenarios()[0]
    pipe = _link.build_pipeline("classical", scn)
    example = template_batch(scn, BATCH, harq=True)
    compiled = get_registry().acquire_pipeline_step(
        pipe, example, batch=BATCH)
    jitted = jax.jit(pipe._apply)  # the pre-registry dispatch path
    jax.block_until_ready(jitted(example))
    jax.block_until_ready(compiled(example))

    def med(fn) -> float:
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(example))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    t_jit, t_aot = med(jitted), med(compiled)
    emit("compile/steady_aot", t_aot * 1e6, f"jit={t_jit * 1e6:.1f}us")
    # gate (b): the registered path is not slower than unregistered
    assert t_aot <= t_jit * PARITY_FACTOR + PARITY_SLACK_S, (t_aot, t_jit)
    return {"aot_step_s": t_aot, "jit_step_s": t_jit, "reps": reps}


def main(json_default: str = ""):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=json_default,
                    help="output JSON path ('' disables)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: warm restart compiles 0 and starts "
                         "faster; AOT steady-state not worse than jit")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the child workload, print stats")
    args, _ = ap.parse_known_args()

    if args.child:
        print(CHILD_MARK + json.dumps(_child_workload()))
        return

    cold_warm = bench_cold_warm()
    parity = bench_steady_parity()
    print(
        f"{'smoke ' if args.smoke else ''}ok: warm restart "
        f"{cold_warm['warm']['time_to_first_tti_s']:.2f}s to first TTI "
        f"vs {cold_warm['cold']['time_to_first_tti_s']:.2f}s cold "
        f"({cold_warm['executables_needed']} executables, 0 recompiled); "
        f"aot step {parity['aot_step_s'] * 1e6:.0f}us "
        f"vs jit {parity['jit_step_s'] * 1e6:.0f}us"
    )

    if args.json and not args.smoke:
        emit_json(args.json, {
            "bench": "compile",
            "n_cells": N_CELLS,
            "n_ticks": N_TICKS,
            "batch": BATCH,
            **cold_warm,
            "steady_parity": parity,
        })


if __name__ == "__main__":
    main(json_default=JSON_PATH)
