"""Bring-up check of the served PHY path on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip: served paths + parity
    python chip_smoke.py --four-chips  # only the (cell, batch) mesh over
                                       # 4 chips against the same seed on 1

One chip, in order:

1. parity — one fixed batch of ``siso-qam16-r12-snr15`` (fused LS-CHE and
   detect+demap) and of ``mimo4x4-qam16-mu-snr18`` (fused SIC) through the
   served step (Pallas kernels), against the jnp twins of the same kernels
   on the same chip and the same inputs: LS-CHE error, LLR sign agreement,
   LDPC iteration counts and hard decisions (fp32 and int8), and the CRC
   outcome of the whole jnp chain;
2. mesh closed loop — 4 ``siso-coded`` cells x 8 users, batch 8, fused
   fp32 classical receiver, through ``MeshSlotScheduler``;
3. open-loop SIC — ``PhyServeEngine`` on ``mimo4x4-qam16-mu-snr18``.

It checks that every served executable holds the Mosaic decoder and the
fused demap (or SIC) kernel, that outputs are finite, that mesh job
conservation is exact, and that no served executable compiles after
warm-up.  Compile counts and cache hits are printed; the compile cache
follows ``JAX_COMPILATION_CACHE_DIR`` (else ``.cache/jax`` here).

Everything runs in this one process, unsupervised (a supervisor would
turn a kernel fault into a silent fallback).  The script exits non-zero,
printing no ``"ok": true``, when JAX finds no TPU or any check fails.  On
success its last line is ``{"ok": true, "device": {...}}``.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
BATCH = 8
MESH_TICKS = 24
WARM_TICKS = 4  # slot generation's own first-shape compiles happen here
SIC_SLOTS = 32
SIGN_AGREEMENT_MIN = 0.999
H_REL_ERR_MAX = 2e-2  # both paths run f32 matmuls at TPU default precision
CRC_FLIPS_MAX_FRAC = 0.01  # of code blocks, and at least 2 blocks
FOUR_CHIP_TICKS = 16
FOUR_CHIP_FLIPS_MAX = 2  # per cell: borderline-LLR flips may move a CRC


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "FAIL  ") + what, flush=True)
    if not ok:
        raise CheckFailed(what)


def mosaic_kernels(compiled) -> list:
    """Op names of the Mosaic kernels in a compiled executable."""
    return [
        line.split('op_name="', 1)[1].split('"', 1)[0]
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def all_finite(state: dict) -> bool:
    return all(
        bool(np.all(np.isfinite(np.asarray(v))))
        for v in state.values()
        if np.issubdtype(np.asarray(v).dtype, np.inexact)
    )


class CompileCounter:
    """Counts true XLA compiles (persistent-cache hits do not fire)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def parity(name: str, options: dict, registry) -> None:
    """Served step (Pallas) vs the jnp twins, same chip, same inputs."""
    import jax

    from repro.kernels import ldpc, rx_fused
    from repro.phy import build_pipeline, coding, ofdm
    from repro.phy.scenarios import get_scenario
    from repro.serve import make_traffic, stack_slots

    scn = get_scenario(name)
    cfg, code = scn.grid, scn.code
    pipe = build_pipeline("classical", scn, **options)
    batch = stack_slots(make_traffic(scn, jax.random.PRNGKey(SEED), BATCH), 0)
    step = registry.acquire_pipeline_step(pipe, batch, batch=BATCH)
    st = jax.block_until_ready(step(batch))
    check(all_finite(st), f"{name}: every served output is finite")

    def smoothed(h_ls):
        """The pipeline's own jnp stages between LS-CHE and detection."""
        s = {"h_ls": h_ls, "noise_var": st["noise_var"]}
        for stage in pipe.stages:
            if stage.name == "mmse_che":
                s = stage.apply(s)
        return s.get("h_hat", h_ls)

    h = st.get("h_hat", st["h_ls"])  # what the served detector saw
    if options.get("fused"):
        op = rx_fused.make_ls_interp_operator(
            cfg.n_subcarriers, cfg.n_tx, cfg.pilot_stride,
            np.asarray(ofdm.pilot_sequence(cfg)),
        )
        h_ls_j = rx_fused.ls_che(st["y"], cfg.pilot_symbols,
                                 cfg.pilot_stride, op, use_pallas=False)
        err = float(np.max(np.abs(np.asarray(st["h_ls"]) - np.asarray(h_ls_j)))
                    / np.max(np.abs(np.asarray(h_ls_j))))
        check(err <= H_REL_ERR_MAX,
              f"{name}: LS-CHE Pallas vs jnp max error {err:.3e} "
              f"(of max |H|; bound {H_REL_ERR_MAX})")
        h_j = smoothed(h_ls_j)
    else:
        h_j = h  # unfused LS-CHE is jnp on every backend

    demap = (rx_fused.sic_detect_demap if options.get("sic")
             else rx_fused.mmse_detect_demap)
    llr_p = np.asarray(st["llr"])
    _, _, llr_same_h = demap(st["y"], h, st["noise_var"], scn.modem,
                             use_pallas=False)
    agree = float(np.mean(np.signbit(llr_p) == np.signbit(llr_same_h)))
    check(agree >= SIGN_AGREEMENT_MIN,
          f"{name}: detect+demap LLR sign agreement {agree:.6f} over "
          f"{llr_p.size} LLRs (bound {SIGN_AGREEMENT_MIN})")

    cw = st["cw_llr"].reshape(-1, code.n_mother)
    it_served = np.asarray(st["decode_iters"]).ravel()
    for precision in ("fp32", "int8"):
        post_p, it_p = ldpc.ldpc_decode(cw, code, use_pallas=True,
                                        precision=precision)
        post_j, it_j = ldpc.ldpc_decode(cw, code, use_pallas=False,
                                        precision=precision)
        it_p, it_j = np.asarray(it_p), np.asarray(it_j)
        same_hard = bool(np.array_equal(np.asarray(post_p) > 0,
                                        np.asarray(post_j) > 0))
        check(np.array_equal(it_p, it_j) and same_hard
              and all_finite({"p": post_p, "j": post_j}),
              f"{name}: LDPC {precision} Pallas vs jnp on {len(cw)} "
              f"codewords: equal iterations (mean {it_p.mean():.3f}, max "
              f"{it_p.max()}) and equal hard decisions")
        if precision == "fp32":
            check(np.array_equal(it_p, it_served),
                  f"{name}: served decoder iterations equal the "
                  f"standalone kernel's")

    # the whole jnp chain: jnp LS-CHE -> jnp demap -> jnp decoder
    _, _, llr_chain = demap(st["y"], h_j, st["noise_var"], scn.modem,
                            use_pallas=False)
    ref = coding.decode_blocks(scn, llr_chain, use_pallas=False)
    crc_p = np.asarray(st["crc_ok"])
    crc_j = np.asarray(ref["crc_ok"])
    flips = int(np.sum(crc_p != crc_j))
    bound = max(2, int(CRC_FLIPS_MAX_FRAC * crc_p.size))
    check(flips <= bound and all_finite(ref),
          f"{name}: CRC outcome differs on {flips} of {crc_p.size} code "
          f"blocks (bound {bound}); BLER Pallas "
          f"{1 - crc_p.mean():.4f} vs jnp {1 - crc_j.mean():.4f}")


def check_served_kernels(registry) -> None:
    for key, compiled in registry.items():
        names = mosaic_kernels(compiled)
        has = lambda k: any(k in n for n in names)
        demap = "rx_sic_demap" if has("rx_sic_demap") else "rx_detect_demap"
        check(has("ldpc_decode") and has(demap),
              f"executable {key.scenario} lanes={key.lanes} "
              f"{key.schema or 'open-loop'}: Mosaic kernels "
              f"{sorted(set(n.rsplit('/', 2)[-2] for n in names))}")


def conservation(sch) -> None:
    ids = sorted(sch.finalized_job_ids() + sch.queued_job_ids())
    check(ids == list(range(sch.jobs_submitted)),
          f"job conservation: {len(sch.finalized_job_ids())} finalized + "
          f"{len(sch.queued_job_ids())} queued == {sch.jobs_submitted} "
          f"submitted, each once")


def one_chip(counter) -> None:
    import math

    import jax

    from repro.serve import (
        FixedBuckets, MeshSlotScheduler, PhyServeEngine, closed_cell,
        get_registry,
    )

    reg = get_registry()
    print(f"compile cache: {reg.cache_dir}", flush=True)
    parity("siso-qam16-r12-snr15", {"fused": True}, reg)
    parity("mimo4x4-qam16-mu-snr18", {"sic": True}, reg)

    # -- mesh closed loop: AOT prebuild is the warm-up -------------------
    cells = [closed_cell(f"cell{i}", "siso-coded", n_users=8, fused=True)
             for i in range(4)]
    # one 4-lane bucket: the prebuild then covers every served step
    sch = MeshSlotScheduler(cells, batch_size=BATCH, seed=SEED,
                            bucket_policy=FixedBuckets([len(cells)]))
    built = reg.stats.executables_compiled + reg.stats.cache_hits
    for _ in range(WARM_TICKS):
        sch.tick()
    xla0 = counter.n
    for _ in range(MESH_TICKS - WARM_TICKS):
        sch.tick()
    rep = sch.report()
    print(rep.summary(), flush=True)
    after = reg.stats.executables_compiled + reg.stats.cache_hits
    check(after == built,
          f"mesh: 0 served executables built after warm-up "
          f"({rep.executables_compiled} compiled, {rep.cache_hits} cache "
          f"hits at warm-up)")
    print(f"info  mesh: XLA compiles in ticks {WARM_TICKS}..{MESH_TICKS}: "
          f"{counter.n - xla0} (host-side slot generation included)",
          flush=True)
    check(rep.n_slots > 0 and rep.blocks_delivered > 0
          and all(math.isfinite(x) for x in (
              rep.first_tx_bler, rep.residual_bler,
              rep.goodput_bits_per_tti, rep.slots_per_sec)),
          f"mesh: {rep.n_slots} slots over {rep.n_ticks} TTIs, "
          f"{rep.blocks_delivered} blocks delivered, finite BLER/goodput")
    conservation(sch)

    # -- open-loop SIC ----------------------------------------------------
    eng = PhyServeEngine.from_scenario(
        "mimo4x4-qam16-mu-snr18", receiver="classical", sic=True,
        batch_size=BATCH,
    )
    eng.submit_traffic(jax.random.PRNGKey(SEED + 1), SIC_SLOTS)
    acquired = reg.lookups
    xla0 = counter.n
    srep = eng.run()
    print(srep.summary(), flush=True)
    check(reg.lookups - acquired == 1,
          f"sic: one executable acquisition for {srep.n_batches} batches "
          f"({srep.executables_compiled} compiled, {srep.cache_hits} hits)")
    print(f"info  sic: XLA compiles while serving: {counter.n - xla0}",
          flush=True)
    check(srep.n_slots == SIC_SLOTS and srep.bler is not None
          and math.isfinite(srep.bler) and math.isfinite(srep.ber),
          f"sic: {srep.n_slots} slots, finite BER {srep.ber:.4f} / "
          f"BLER {srep.bler:.4f}")

    check_served_kernels(reg)
    r = reg.report()
    print(f"registry: {r['executables_compiled']} executables compiled, "
          f"{r['cache_hits']} cache hits, {r['compile_time_s']:.1f} s "
          f"compiling, {r['resident']} resident", flush=True)


def four_chips() -> None:
    import jax
    from jax.sharding import Mesh

    from repro.launch.mesh import make_cell_mesh
    from repro.serve import (
        FixedBuckets, MeshSlotScheduler, closed_cell, get_registry,
    )

    devices = jax.devices()
    check(len(devices) >= 4, f"{len(devices)} devices (need 4)")
    n_cells = 8
    cells = [closed_cell(f"cell{i}", "siso-coded", n_users=8, fused=True)
             for i in range(n_cells)]
    meshes = {
        "4-device": make_cell_mesh(n_cells),
        "1-device": Mesh(np.asarray(devices[:1]).reshape(1, 1),
                         ("cell", "batch")),
    }
    reps = {}
    for tag, mesh in meshes.items():
        sch = MeshSlotScheduler(cells, batch_size=BATCH, seed=SEED,
                                mesh=mesh,
                                bucket_policy=FixedBuckets([n_cells]))
        reps[tag] = sch.run(FOUR_CHIP_TICKS)
        print(f"{tag} mesh {tuple(mesh.devices.shape)}: "
              f"{reps[tag].summary()}", flush=True)
        conservation(sch)

    spans = {
        len(s.device_set)
        for key, compiled in get_registry().items()
        if key.mesh.startswith("4x1@")
        for s in jax.tree.leaves(compiled.input_shardings)
    }
    check(spans == {4}, f"4-device steps: staged inputs span {spans} "
                        f"devices")
    for name in sorted(reps["4-device"].cells):
        a, b = reps["4-device"].cells[name], reps["1-device"].cells[name]
        blocks = max(1, b.blocks_delivered + b.blocks_lost)
        check(abs(a.blocks_delivered - b.blocks_delivered)
              <= FOUR_CHIP_FLIPS_MAX
              and abs(a.blocks_lost - b.blocks_lost) <= FOUR_CHIP_FLIPS_MAX
              and abs(a.first_tx_bler - b.first_tx_bler)
              <= FOUR_CHIP_FLIPS_MAX / blocks,
              f"{name}: delivered {a.blocks_delivered} vs "
              f"{b.blocks_delivered}, lost {a.blocks_lost} vs "
              f"{b.blocks_lost}, first-tx BLER {a.first_tx_bler:.4f} vs "
              f"{b.first_tx_bler:.4f} (4-device vs 1-device)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phase")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL  JAX found no TPU (platform {dev.platform!r})")
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    counter = CompileCounter()
    try:
        if args.four_chips:
            four_chips()
        else:
            one_chip(counter)
    except CheckFailed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
