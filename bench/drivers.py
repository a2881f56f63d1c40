"""The two ways a cell drives the program: closed loop and backlog.

Each driver builds the program's serving object in set-up, warms every
shape its window uses, serves for a fixed wall time, and records the
counters the metric readers use plus a sample of its answers for the
comparison with the plain reference.  The program is given only inputs:
the closed loop's slots come from the program's own ``CellLoop`` seeded
from ``--seed``; the backlog's pool comes from :mod:`slotgen`.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import checks
import phy
import slotgen
import system


class CompileCounter:
    """Counts executables that XLA built or loaded from the persistent
    cache (``n``; JAX records its compile event around both), and the
    persistent-cache loads among them (``hits``)."""

    def __init__(self):
        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def seed_words(seed: int, salt: str, n: int = 1) -> list:
    """``n`` 31-bit words from ``--seed`` (any size) and a purpose."""
    ss = np.random.SeedSequence([seed % 2**63, (seed // 2**63) % 2**63] +
                                [ord(ch) for ch in salt])
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n)]


class Window:
    """What one measured window did: the readers' input."""

    def __init__(self, kind: str):
        self.kind = kind
        self.window_s = 0.0
        self.slots = 0
        self.info_bits_ok = 0.0
        self.tick_s: list = []  # closed loop: wall time of each tick
        self.dispatch_s: list = []  # closed loop: step window per tick
        self.slots_by_rung = {}
        self.filler_lanes = 0
        self.lanes_staged = 0
        self.compiles = 0
        self.trace = None  # xtrace.Summary of a traced run


class ClosedLoop:
    """``MeshSlotScheduler.tick()`` over ``n_cells`` x ``n_users`` with
    Poisson arrivals, HARQ and OLLA, in TTI lockstep."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 counter: CompileCounter):
        from jax.sharding import Mesh
        from repro.serve import FixedBuckets, MeshSlotScheduler, closed_cell

        self.config, self.traffic, self.counter = config, traffic, counter
        self.rungs = phy.rungs(config)
        ladder = system.register(config)
        t = traffic
        specs = [
            closed_cell(f"cell{i}", ladder, config["receiver"]["kind"],
                        n_users=t["n_users"], arrival_rate=t["arrival_rate"],
                        snr_spread_db=t["snr_spread_db"],
                        init_mcs=t["init_mcs"], **system.receiver_options(config))
            for i in range(t["n_cells"])
        ]
        n_dev = len(devices)
        cell_axis = int(np.gcd(t["n_cells"], n_dev))
        mesh = Mesh(np.asarray(devices).reshape(cell_axis, n_dev // cell_axis),
                    ("cell", "batch"))
        h = config["harq"]
        observer = self

        class Scheduler(MeshSlotScheduler):
            def _feedback(self, lanes, mcs, state, stats):
                before = observer.before(lanes)
                super()._feedback(lanes, mcs, state, stats)
                observer.after(lanes, mcs, state, before)

        self.sch = Scheduler(
            specs, batch_size=config["batch"], mesh=mesh,
            max_retx=h["max_retx"], deadline_ttis=t["deadline_ttis"],
            target_bler=h["target_bler"], olla_step=h["olla_step"],
            seed=seed, bucket_policy=FixedBuckets(t["buckets"]),
        )
        self.bucket = max(t["buckets"])
        word = seed_words(seed, "sample")[0]
        self.sampled = np.random.default_rng(word).random(1 << 20) < \
            t["sample_share"]
        self.recording = False
        self.win = Window("closed_loop")
        self.samples = {}  # job id -> [transmission records]
        self.t_build = time.perf_counter()
        self.warm(t["warm_ticks"])

    def warm(self, n_ticks: int) -> None:
        """Compile slot generation of every rung at every RV it can send,
        then run ticks until the queues reach their steady state."""
        from repro.phy import coding
        from repro.phy.scenarios import get_scenario

        key = jax.random.PRNGKey(0)
        for r in self.rungs:
            scn = get_scenario(system.scenario_name(self.config, r.name))
            first = coding.make_coded_slot(key, scn, 1, rv=0)
            for rv in range(1, self.config["harq"]["max_retx"] + 1):
                coding.make_coded_slot(key, scn, 1, rv=rv,
                                       info=first["info_bits"])
        for _ in range(n_ticks):
            self.sch.tick()

    # -- observation, on the program's feedback path ---------------------
    def before(self, lanes) -> list:
        return [[(job.job_id, job.harq.n_tx) for _, job in lane.pairs]
                for lane in lanes]

    def after(self, lanes, mcs: int, state: dict, before: list) -> None:
        """Count the step and keep references to the sampled jobs' slots
        and answers; nothing is copied to the host inside the window."""
        if not self.recording:
            return
        rung = self.rungs[mcs].name
        w = self.win
        n = sum(len(lane.pairs) for lane in lanes)
        w.slots_by_rung[rung] = w.slots_by_rung.get(rung, 0) + n
        w.lanes_staged += self.bucket
        w.filler_lanes += self.bucket - len(lanes)
        outs = (state["cw_llr"], state["crc_ok"], state["decode_iters"])
        for li, lane in enumerate(lanes):
            for j, (jid, n_tx) in enumerate(before[li]):
                if (self.sampled[jid % len(self.sampled)]
                        and (n_tx == 0 or jid in self.samples)):
                    self.samples.setdefault(jid, []).append(
                        (rung, n_tx, lane.slots[j], outs, li, j))

    # -- the measured window ---------------------------------------------
    def window(self, seconds: float) -> Window:
        w = self.win
        rep0 = self.sch.report()
        bits0 = sum(loop.good_bits() for loop in self.sch.loops)
        c0 = self.counter.n
        self.recording = True
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() - t_start < seconds:
                d0 = self.sch.wall_s
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("tick"):
                    self.sch.tick()
                w.tick_s.append(time.perf_counter() - t0)
                w.dispatch_s.append(self.sch.wall_s - d0)
        w.window_s = time.perf_counter() - t_start
        self.recording = False
        w.compiles = self.counter.n - c0
        rep1 = self.sch.report()
        w.slots = rep1.n_slots - rep0.n_slots
        w.info_bits_ok = sum(loop.good_bits() for loop in self.sch.loops) \
            - bits0
        self.conservation_ok = self.check_conservation()
        return w

    def check_conservation(self) -> bool:
        """Every job issued is finalized or queued, exactly once."""
        ids = sorted(self.sch.finalized_job_ids() + self.sch.queued_job_ids())
        return ids == list(range(self.sch.jobs_submitted))

    def release(self) -> None:
        del self.sch

    def reference_inputs(self) -> list:
        """HARQ chains of the sampled jobs, transmission by transmission,
        brought to the host once the window has closed."""
        chains = []
        for _, txs in sorted(self.samples.items()):
            chain = []
            for rung, n_tx, slot, (cw, crc, its), li, j in sorted(
                    txs, key=lambda t: t[1]):
                chain.append({
                    "rung": rung, "tx": n_tx,
                    "y_time": np.asarray(slot["y_time"])[0],
                    "noise_var": float(np.asarray(slot["noise_var"])),
                    "rv": int(np.asarray(slot["rv"])[0]),
                    "cw_llr": np.asarray(cw)[li, j],
                    "crc_ok": np.asarray(crc)[li, j],
                    "iters": np.asarray(its)[li, j],
                })
            chains.append(chain)
        return chains

    def readings(self, control: bool) -> tuple:
        """The program's numbers against the float32 reference, and with
        ``control`` those of the reference computed in bfloat16 in the
        program's place; every sampled job is replayed in HARQ order."""
        chains = self.reference_inputs()
        self.samples = {}
        rungs = {r.name: r for r in self.rungs}
        ref = checks.replay(chains, rungs)
        pairs = [(t, r) for c, rs in zip(chains, ref) for t, r in zip(c, rs)]
        got = checks.numbers(pairs, pairs)
        if not control:
            return got, None
        ctl = checks.replay(chains, rungs, "bfloat16")
        pairs = [(t, r) for cs, rs in zip(ctl, ref) for t, r in zip(cs, rs)]
        return got, checks.numbers(pairs, pairs)


class Backlog:
    """``PhyServeEngine.run()`` over a pool of slots resubmitted round
    after round, so the queue never empties.

    The reference replays ``ref_batches`` batch-aligned groups of pool
    slots drawn from the seed: the LLRs of each at its first service in
    the window, and the CRC outcomes and decoder iterations of every
    service of them."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 counter: CompileCounter):
        from repro.phy.scenarios import get_scenario
        from repro.serve import PhyServeEngine
        from repro.serve.runtime import BatchRunner

        self.config, self.traffic, self.counter = config, traffic, counter
        system.register(config)
        self.rung = phy.rung(config, traffic["rung"])
        scn = get_scenario(system.scenario_name(config, self.rung.name))
        observer = self

        class Runner(BatchRunner):
            def run_batch(self, reqs):
                state = super().run_batch(reqs)
                observer.after(reqs, state)
                return state

        class Engine(PhyServeEngine):
            def _make_runner(self):
                return Runner(self.pipeline, self.batch_size)

        batch = config["batch"]
        n = traffic["pool_slots"]
        with jax.default_device(devices[0]):
            self.engine = Engine.from_scenario(
                scn, config["receiver"]["kind"], batch_size=batch,
                **system.receiver_options(config))
            word = seed_words(seed, "pool")[0]
            self.pool = slotgen.make_pool(self.rung, jax.random.PRNGKey(word),
                                          n, traffic["pool_chunk"])
        rng = np.random.default_rng(seed_words(seed, "sample")[0])
        groups = rng.choice(n // batch, traffic["ref_batches"], replace=False)
        self.ref_idx = np.sort(np.concatenate(
            [np.arange(g * batch, (g + 1) * batch) for g in groups]))
        self.recording = False
        self.want_llr = set()  # reference slots whose LLRs are still due
        self.blocks = []  # (pool indices, crc_ok, iters) of every batch
        self.samples = []  # (pool indices, cw_llr) on the host
        self.pending = None  # the newest sample, still copying to the host
        self.win = Window("backlog")
        self.t_build = time.perf_counter()
        # every batch has the same shape: a few warm the executable and
        # the engine's host path
        self.serve(range(traffic["warm_batches"] * batch))

    def after(self, reqs, state) -> None:
        if not self.recording:
            return
        idx = [r.user_id for r in reqs]
        self.blocks.append((idx, state["crc_ok"], state["decode_iters"]))
        if self.want_llr.intersection(idx):
            self.want_llr.difference_update(idx)
            self.flush()
            state["cw_llr"].copy_to_host_async()
            self.pending = (idx, state["cw_llr"])

    def flush(self) -> None:
        """Keep at most one sampled batch on the device."""
        if self.pending is not None:
            idx, cw = self.pending
            self.samples.append((idx, np.asarray(cw)[: len(idx)]))
            self.pending = None

    def serve(self, indices) -> int:
        with jax.profiler.TraceAnnotation("submit"):
            for i in indices:
                self.engine.submit(self.pool[i], user_id=int(i))
        with jax.profiler.TraceAnnotation("run"):
            rep = self.engine.run()
        return rep.n_slots

    def window(self, seconds: float) -> Window:
        w = self.win
        c0 = self.counter.n
        self.want_llr = set(self.ref_idx.tolist())
        self.recording = True
        self.conservation_ok = True  # every round serves the whole pool
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() - t_start < seconds:
                n = self.serve(range(len(self.pool)))
                w.slots += n
                self.conservation_ok &= n == len(self.pool)
        w.window_s = time.perf_counter() - t_start
        self.recording = False
        w.compiles = self.counter.n - c0
        w.slots_by_rung[self.rung.name] = w.slots
        self.blocks = [(i, np.asarray(c)[: len(i)], np.asarray(t)[: len(i)])
                       for i, c, t in jax.device_get(self.blocks)]
        w.info_bits_ok = float(sum(c.sum() for _, c, _ in self.blocks)
                               * self.rung.code.k_info)
        return w

    def release(self) -> None:
        self.flush()
        del self.engine

    def readings(self, control: bool) -> tuple:
        """The program's numbers against the float32 reference over the
        reference slots, and with ``control`` those of the reference
        computed in bfloat16 in the program's place."""
        ys = np.concatenate([self.pool[i]["y_time"] for i in self.ref_idx])
        ref = checks.pool_reference(ys, self.rung)
        pos = {int(i): k for k, i in enumerate(self.ref_idx)}
        llr = [([pos[i] for i in idx if i in pos],
                cw[[j for j, i in enumerate(idx) if i in pos]])
               for idx, cw in self.samples]
        blk = [([pos[i] for i in idx if i in pos],
                c[[j for j, i in enumerate(idx) if i in pos]],
                t[[j for j, i in enumerate(idx) if i in pos]])
               for idx, c, t in self.blocks]
        got = checks.backlog(llr, blk, ref)
        if not control:
            return got, None
        ctl = checks.pool_reference(ys, self.rung, "bfloat16")
        return got, checks.backlog(
            [(i, ctl["cw_llr"][i]) for i, _ in llr],
            [(i, ctl["crc_ok"][i], ctl["iters"][i]) for i, _, _ in blk], ref)


KINDS = {"closed_loop": ClosedLoop, "backlog": Backlog}
