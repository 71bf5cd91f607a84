"""Driver of the simulator cells: sweep points of FLySTacK over one world.

Set-up builds the world once (the program's contact plan, timed as the
world build, and the benchmark's dataset from the seed), then runs one
short warm-up job that compiles or loads every program the window uses.
The window runs jobs back to back. A job is one sweep point: a new engine
with its own FL seed, drawn from the run's seed and the job's index, on
the same world, run for the traffic's ``rounds_per_job`` rounds or to the
horizon. The window closes at the end of the first job that ends after
the window's length.

The engine is built as ``FLySTacK.run()`` builds it, so that the final
global model of each job can be compared; the program gets only the
generated world, data and the settings the traffic names.
"""
from __future__ import annotations

import dataclasses
import time
import traceback

import jax
import numpy as np

from bench import compare, counts, data
from bench.reference import fl as ref_fl
from bench.reference import world as ref_world

MASK31 = 0x7FFFFFFF


def seed_words(seed: int, *salt: int, n: int = 1):
    """``n`` 31-bit seeds drawn from the run's seed (any whole number) and
    ``salt``."""
    ss = np.random.SeedSequence([seed % 2 ** 64, *salt])
    return [int(s) & MASK31 for s in ss.generate_state(n)]


class Sim:
    """One simulator cell: ``config`` and ``traffic`` are the parsed
    files, ``seed`` the run's seed, ``log`` prints to standard error."""

    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config, self.traffic, self.seed, self.log = config, traffic, \
            seed, log
        self.fl = {**config["fl"], **traffic["fl"]}
        self.algorithm = traffic["algorithm"]
        self.rounds_per_job = int(traffic["rounds_per_job"])
        self.world = ref_world.World.from_config(config)
        self.jobs = []
        self._params = []
        self.window_rounds = 0

    # -- the program ---------------------------------------------------
    def _sim_config(self, fl_seed: int, max_rounds: int):
        from repro.core.spaceify import FLConfig
        from repro.sim.flystack import SimConfig
        w = self.world
        fl = FLConfig(model="cnn", seed=fl_seed, max_rounds=max_rounds,
                      **self.fl)
        return SimConfig(algorithm=self.algorithm, n_clusters=w.planes,
                         sats_per_cluster=w.per_plane,
                         n_ground_stations=len(w.stations),
                         horizon_days=self.config["horizon_days"],
                         dt_s=w.dt_s, min_elev_deg=w.min_elev_deg, fl=fl,
                         epochs_mode=self.traffic.get("epochs_mode", "fixed"),
                         seed=self.data_seed)

    def _engine(self, fl_seed: int, max_rounds: int):
        """The algorithm object exactly as ``FLySTacK.run()`` builds it."""
        from repro.core.autoflsat import AutoFLSat
        from repro.core.spaceify import ALGORITHMS
        cfg = self._sim_config(fl_seed, max_rounds)
        if cfg.algorithm == "autoflsat":
            return AutoFLSat(self.plan, self.hw, self.ds, cfg.fl,
                             epochs_mode=cfg.epochs_mode)
        cls, overrides = ALGORITHMS[cfg.algorithm]
        return cls(self.plan, self.hw, self.ds,
                   dataclasses.replace(cfg.fl, **overrides))

    def make_data(self) -> None:
        """The run's dataset on the device, from the seed."""
        self.data_seed, self.warm_seed, self.sample_seed = seed_words(
            self.seed, 0, n=3)
        self.ds = data.make(self.config["data"], self.world.n_sats,
                            self.data_seed)
        jax.block_until_ready(self.ds.x)

    def setup(self) -> None:
        from repro.sim import hardware
        from repro.sim.flystack import FLySTacK
        self.make_data()
        self.hw = getattr(hardware, self.config["hardware"]["profile"])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.world_build"):
            self.plan = FLySTacK(self._sim_config(0, 1), hw=self.hw,
                                 dataset=self.ds).plan
        self.world_build_s = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self._job(self.warm_seed, int(self.traffic["warmup_rounds"]))

    def _job(self, fl_seed: int, rounds: int):
        with jax.profiler.TraceAnnotation("bench.engine_init"):
            algo = self._engine(fl_seed, rounds)
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            recs = algo.run()
            jax.block_until_ready(algo.global_params)
        return algo, recs

    # -- the window ----------------------------------------------------
    def job_seed(self, j: int) -> int:
        return seed_words(self.seed, 1, j)[0]

    def window(self, seconds: float):
        """Run jobs for ``seconds``; returns (attempted, failed, elapsed)
        in rounds and host seconds."""
        attempted = failed = 0
        t0 = time.perf_counter()
        j = 0
        while True:
            fl_seed = self.job_seed(j)
            j += 1
            try:
                algo, recs = self._job(fl_seed, self.rounds_per_job)
            except Exception:                     # a failed sweep point
                self.log(traceback.format_exc())
                attempted += self.rounds_per_job
                failed += self.rounds_per_job
            else:
                rounds = [(r.t_start, r.t_end, list(r.participants),
                           r.epochs, r.accuracy) for r in recs]
                attempted += len(rounds)
                failed += sum(not np.isfinite(r[4]) for r in rounds)
                self.jobs.append(compare.Job(fl_seed, rounds))
                self._params.append(algo.global_params)
                del algo
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_rounds = sum(len(j.rounds) for j in self.jobs)
        return attempted, failed, time.perf_counter() - t0

    def end_to_end(self, elapsed: float) -> dict:
        """The window's end-to-end metrics, by name, besides ``setup_s``."""
        return {"rounds_per_s": self.window_rounds / elapsed}

    # -- correctness ---------------------------------------------------
    def reference(self, dtype=np.float64):
        """The reference schedule, [(t_start, t_end, participants,
        epochs)] per round, and for FedBuff the returns it trains, from
        the reference world in ``dtype``."""
        w, fl, n = self.world, self.fl, self.rounds_per_job
        if self.algorithm == "autoflsat":
            rounds = ref_world.autoflsat_schedule(
                w, ref_world.all_pair_windows(w, dtype), self.model_bytes(),
                fl.get("max_local_epochs", 30), n)
            return [(a, b, list(range(w.n_sats)), e) for a, b, e in rounds], \
                None
        windows = ref_world.ground_windows(w, dtype)
        if self.algorithm == "fedbuff":
            return ref_world.fedbuff_schedule(
                w, windows, self.model_bytes(), fl["buffer_size"],
                fl.get("max_local_epochs", 30), n)
        rounds = ref_world.fedavg_schedule(
            w, windows, self.model_bytes(), fl["clients_per_round"],
            fl["epochs"], n)
        return [(a, b, sel, fl["epochs"]) for a, b, sel in rounds], None

    def n_params(self):
        d = self.config["data"]
        return counts.cnn_params(tuple(d["image_shape"]),
                                 self.config["model"]["width"],
                                 d["n_classes"])

    def model_bytes(self) -> float:
        """Bytes of one transmitted model: 4 per weight, or ``bits`` per
        weight and one f32 scale per tensor on the QuAFL wire."""
        n, tensors = self.n_params()
        bits = self.fl.get("quant_bits", 0)
        return n * bits / 8 + tensors * 4 if bits else n * 4.0

    def replay(self, job: compare.Job, ref, dtype=None):
        """The reference's replay of ``job`` on the reference schedule
        ``ref``: (final params, accuracy per round, initial params)."""
        rounds, events = ref
        rounds = rounds[:len(job.rounds)]
        m = self.config["model"]
        rep = ref_fl.Replay(self.ds.arrays(), self.fl, m["width"],
                            self.config["data"]["n_classes"],
                            precision=m["matmul_precision"],
                            **({} if dtype is None else {"dtype": dtype}))
        if self.algorithm == "autoflsat":
            return rep.autoflsat(job.seed, [(a, b, e) for a, b, _, e in
                                            rounds], self.world.planes)
        if self.algorithm == "fedbuff":
            return rep.fedbuff(job.seed, events, len(rounds))
        return rep.fedavg(job.seed, [r[:3] for r in rounds])

    def check(self) -> dict:
        """The compared numbers: every job's records against the
        reference schedule, and one job drawn from the seed replayed by
        the reference."""
        self.ref = self.reference()
        numbers = compare.schedule_numbers(self.jobs, self.ref[0])
        if not self.jobs:
            return {**numbers, "accuracy_gap": float("inf"),
                    "model_change_gap": float("inf"),
                    "model_diff": float("inf")}
        pick = int(np.random.default_rng(self.sample_seed).integers(
            len(self.jobs)))
        job = self.jobs[pick]
        job.params = {k: np.asarray(v) for k, v in
                      self._params[pick].items()}
        self._params.clear()
        p, acc, p0 = self.replay(job, self.ref)
        numbers.update(compare.model_numbers(
            job, {k: np.asarray(v, np.float32) for k, v in p.items()},
            {k: np.asarray(v, np.float32) for k, v in p0.items()}, acc))
        return numbers

    # -- what the per-layer readers need --------------------------------
    def model_flops(self) -> float:
        """Model FLOPs of every round in the window: each real
        participant's forward and backward passes over the samples its
        epochs visit, and the test set's forward pass."""
        d, m = self.config["data"], self.config["model"]
        shape = tuple(d["image_shape"])
        train = counts.cnn_train_flops(shape, m["width"], d["n_classes"])
        fwd = counts.cnn_forward_flops(shape, m["width"], d["n_classes"])
        bs = self.fl["batch_size"]
        seen = (d["per_client"] // bs) * bs
        events = self.ref[1]
        total = 0.0
        for job in self.jobs:
            total += len(job.rounds) * d["n_test"] * fwd
            if events is not None:      # FedBuff: one client per return
                n = len(job.rounds) * int(self.fl["buffer_size"])
                total += sum(ep for _, ep, _ in events[:n]) * seen * train
                continue
            for _, _, sel, ep, _ in job.rounds:
                total += len(sel) * float(ep) * seen * train
        return total

    def quant_agg_need(self):
        """(operations, bytes) of the server's quantized means in the
        window, one per round, over its real participants."""
        bits = self.fl.get("quant_bits", 0)
        if not bits:
            return None
        n_params, tensors = self.n_params()
        ops = nbytes = 0.0
        for job in self.jobs:
            for r in job.rounds:
                o, b = counts.quant_agg_need(len(r[2]), n_params,
                                             tensors, bits)
                ops, nbytes = ops + o, nbytes + b
        return ops, nbytes
