"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell against
the production mesh with 512 host placeholder devices, then extract the
roofline profile from the compiled artifact.

MUST be run as its own process (device count is locked at first jax init):

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis.costmodel import MeshShape, hbm_traffic  # noqa: E402
from repro.analysis.hloparse import profile_hlo  # noqa: E402
from repro.analysis.roofline import (  # noqa: E402
    active_params,
    build_report,
    model_flops_ideal,
)
from repro.common.params import count_params, schema_shapes  # noqa: E402
from repro.configs import (  # noqa: E402
    SHAPES,
    TrainConfig,
    applicable_shapes,
    get_config,
)
from repro.configs.registry import ARCH_IDS  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.train import step as step_lib  # noqa: E402


def _serving_param_specs(model):
    """Parameters in serving dtype (bf16) as ShapeDtypeStructs."""
    shapes = schema_shapes(model.schema())
    dt = model.cfg.dtype()

    def cast(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(s.shape, dt)
        return s

    return jax.tree.map(cast, shapes)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_override=None, mode: str = "base", microbatches: int = 1):
    cfg = get_config(arch)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        # production numeric policy: bf16 params, fp32 Adam moments
        cfg = cfg.replace(param_dtype="bfloat16")
    model = get_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    pshard = shd.param_shardings(model, mesh, mode=mode)
    batch_specs = model.input_specs(shape)
    rules = (shd.ACT_RULES_FSDP if mode == "fsdp" else shd.ACT_RULES)
    batch_sh = {
        k: jax.sharding.NamedSharding(
            mesh,
            shd.spec_for(tuple(v.shape),
                         ("batch",) + (None,) * (len(v.shape) - 1),
                         rules, mesh),
        )
        for k, v in batch_specs.items()
    }

    t0 = time.perf_counter()
    ctx = shd.activation_mesh(mesh, mode=mode)
    ctx.__enter__()
    if shape.kind == "train":
        tc = TrainConfig(microbatches=microbatches)
        step_fn = step_lib.make_train_step(model, tc)
        state_spec = jax.eval_shape(
            lambda k: step_lib.init_state(model, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        state_sh = {
            "params": pshard,
            "opt": shd.opt_state_shardings(pshard, mesh),
        }
        fn = jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        lowered = fn.lower(state_spec, batch_specs)
    elif shape.kind == "prefill":
        pspec = _serving_param_specs(model)
        cache_spec = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        cache_sh = shd.cache_shardings(cfg, cache_spec, mesh)
        fn = jax.jit(
            lambda p, b, c: model.prefill(p, b, c),
            in_shardings=(pshard, batch_sh, cache_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,),
        )
        lowered = fn.lower(pspec, batch_specs, cache_spec)
    elif shape.kind == "decode":
        pspec = _serving_param_specs(model)
        cache_spec = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        cache_sh = shd.cache_shardings(cfg, cache_spec, mesh)
        tok_sh = batch_sh["tokens"]
        fn = jax.jit(
            lambda p, t, c: model.decode_step(p, t, c),
            in_shardings=(pshard, tok_sh, cache_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,),
        )
        lowered = fn.lower(pspec, batch_specs["tokens"], cache_spec)
    else:
        raise ValueError(shape.kind)
    ctx.__exit__(None, None, None)

    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}
    text = compiled.as_text()
    prof = profile_hlo(text)

    n_params = count_params(model.schema())
    n_active = active_params(cfg, n_params)
    mf = model_flops_ideal(cfg, shape, n_active)

    mesh_name = "2x16x16" if multi_pod else "16x16"
    traffic = hbm_traffic(cfg, shape, MeshShape.from_multipod(multi_pod))
    rep = build_report(
        cell=f"{arch}:{shape_name}",
        mesh_name=mesh_name,
        chips=chips,
        prof=prof,
        model_flops_global=mf,
        mem_stats=mem,
        xla_flops_raw=float(ca.get("flops", 0.0)),
        hbm_bytes_model=traffic["total"],
    )
    result = rep.to_json()
    result.update(
        n_params=n_params,
        n_params_active=n_active,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        hlo_bytes=len(text),
        status="ok",
    )
    return result, rep


def run_cell(arch, shape_name, multi_pod, out_dir=None, verbose=True,
             mode="base", microbatches=1, tag_suffix=""):
    tag = (f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
           f"{tag_suffix}")
    try:
        result, rep = lower_cell(arch, shape_name, multi_pod, mode=mode,
                                 microbatches=microbatches)
        if verbose:
            print(rep.row())
            print(
                f"    args={result['arg_bytes']/1e9:.2f}GB "
                f"temp={result['temp_bytes']/1e9:.2f}GB "
                f"fits={result['fits_hbm']} "
                f"compile={result['compile_s']}s "
                f"colls={result['collective_counts']}"
            )
    except Exception as e:
        result = {
            "cell": f"{arch}:{shape_name}",
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }
        if verbose:
            print(f"{tag}: ERROR {type(e).__name__}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--mode", default="base",
                    choices=["base", "sp", "fsdp", "serve_tp"],
                    help="sharding mode (perf variants)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="", help="suffix for the output JSON")
    args = ap.parse_args()

    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape_name in applicable_shapes(cfg):
                meshes = [False, True] if args.both_meshes else [args.multipod]
                for mp in meshes:
                    run_cell(arch, shape_name, mp, out_dir=args.out)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    meshes = [False, True] if args.both_meshes else [args.multipod]
    for mp in meshes:
        run_cell(args.arch, args.shape, mp, out_dir=args.out, mode=args.mode,
                 microbatches=args.microbatches, tag_suffix=args.tag)


if __name__ == "__main__":
    main()
