"""Smoke run of FLySTacK's training path on TPU.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chip   # four chips: the LM trainer's sync

One chip: one contact plan (the paper's 10x10 Walker-star, 3 ground
stations, 1.5 days on a 60 s grid) and one synthetic EuroSAT dataset feed
two three-round FedAvg simulations through ``FLySTacK(...).run()``: one
with 8-bit QuAFL transmission, whose server runs the ``quant_agg`` kernel,
and one with the trimmed-mean server, which runs the ``trimmed_agg``
kernel. The script checks that the kernels' automatic route is the
compiled kernel, that each kernel matches its jnp oracle on one trained
cohort, and that both runs end above chance accuracy.

Four chips: ``repro.launch.train``'s hierarchical trainer for mamba2-1.3b
at full width, 2 clusters on a (pod=2, data=1, model=2) mesh: one round
of the trainer's loop (a few local steps and one tier-2 sync), and a
check that both cluster replicas then equal the host-side mean of their
pre-sync values.

Everything runs in this one process. Without a TPU it exits nonzero and
prints no result. The last line of standard output is the JSON result,
printed only when every check passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from functools import partial

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.aggregation import (make_robust_aggregator,  # noqa: E402
                                    quantized_weighted_average)
from repro.core.client import local_sgd_clients  # noqa: E402
from repro.core.quantize import quantize_stacked  # noqa: E402
from repro.core.spaceify import FLConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.small import MODELS  # noqa: E402
from repro.optim.optimizers import AdamWConfig  # noqa: E402
from repro.sim.flystack import FLySTacK, SimConfig  # noqa: E402

WORLD = dict(algorithm="fedavg", n_clusters=10, sats_per_cluster=10,
             n_ground_stations=3, dataset="eurosat", model="cnn",
             horizon_days=1.5, dt_s=60.0, n_per_client=64)
FL = dict(clients_per_round=50, epochs=2, max_rounds=3, lr=0.05)
#: the two runs, each routing its server through one aggregation kernel
RUNS = {"quant_agg": {"quant_bits": 8},
        "trimmed_agg": {"aggregator": "trimmed_mean"}}

#: Kernel vs jnp oracle. Both sum at most 50 f32 terms below 1 in magnitude,
#: in different orders, so they may differ by a few ulps of the sum.
KERNEL_RTOL = KERNEL_ATOL = 1e-5
CHANCE = 0.1    # ten EuroSAT classes
#: Accuracy after the last round must reach CHANCE + MARGIN.
MARGIN = 0.1

#: The LM trainer's four-chip path, as ``repro.launch.train`` arguments.
HFL_ARGS = ["--arch", "mamba2-1.3b", "--hfl", "--clusters", "2",
            "--steps", "3", "--sync-every", "3", "--batch", "4",
            "--seq", "512", "--dtype", "bfloat16"]
#: Parameter leaves whose replicas the sync check compares: the embedding
#: and one layer's conv weights are sharded over ``model``, the others not.
SYNC_LEAVES = ("['tok_embed']", "['final_norm']['scale']",
               "['layers'][0]['ssm']['conv_w']",
               "['layers'][0]['ssm']['dt_bias']")
#: Post-sync replica vs host f32 mean: the device computes the same mean.
SYNC_TOL = 1e-6


def require_tpu():
    """JAX's devices, or an error naming the platform found instead; the
    smoke run never falls back to the CPU."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is on platform "
                           f"{platform!r}")
    return devices


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CacheEvents:
    """Counts persistent compilation-cache hits and writes."""

    def __init__(self):
        self.hits = self.writes = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


# ---------------------------------------------------------------------------
# one chip: FLySTacK simulations through both aggregation kernels
# ---------------------------------------------------------------------------


def sim_configs(seed: int, world=WORLD, fl=FL) -> dict:
    """One ``SimConfig`` per run in ``RUNS``, all on the same world."""
    return {name: SimConfig(seed=seed, **world,
                            fl=FLConfig(seed=seed, quant_kernel="auto",
                                        **fl, **extra))
            for name, extra in RUNS.items()}


def simulate(cfgs: dict):
    """Build the contact plan and dataset once, then run every config on
    them. Returns ({name: SimResult}, dataset)."""
    t0 = time.perf_counter()
    world = FLySTacK(next(iter(cfgs.values())))
    jax.block_until_ready(world.dataset.x)
    print(f"set-up (contact plan + dataset): "
          f"{time.perf_counter() - t0:.1f} s wall", flush=True)
    results = {}
    for name, cfg in cfgs.items():
        t0 = time.perf_counter()
        results[name] = FLySTacK(cfg, plan=world.plan,
                                 dataset=world.dataset).run()
        acc = [r.accuracy for r in results[name].records]
        print(f"run {name}: {time.perf_counter() - t0:.1f} s wall, "
              f"accuracy per round {acc}", flush=True)
    return results, world.dataset


def check_accuracy(name: str, result, rounds: int) -> None:
    acc = [r.accuracy for r in result.records]
    check(len(acc) == rounds, f"{name} ran {len(acc)} of {rounds} rounds")
    check(all(math.isfinite(a) for a in acc),
          f"{name} accuracy is not finite: {acc}")
    check(acc[-1] >= CHANCE + MARGIN,
          f"{name} accuracy {acc[-1]} after {rounds} rounds is below "
          f"chance {CHANCE} + margin {MARGIN}")


def trained_cohort(cfg: SimConfig, dataset):
    """One cohort trained as the round engine trains it: the first
    ``clients_per_round`` clients from one seeded model, stacked (C, ...)."""
    fl = cfg.fl
    width = fl.clients_per_round
    key, init_key = jax.random.split(jax.random.PRNGKey(fl.seed))
    params = MODELS[fl.model][0](init_key, tuple(dataset.x.shape[2:]),
                                 dataset.n_classes)
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p, (width,) + p.shape), params)
    return local_sgd_clients(fl.model, stacked, dataset.x[:width],
                             dataset.y[:width], jax.random.split(key, width),
                             fl.epochs, fl.batch_size, fl.lr)


def kernel_routes(cohort) -> dict:
    """The route ``"auto"`` resolves to, and whether each aggregation op,
    lowered as the server calls it, contains a compiled TPU kernel."""
    leaf = max(jax.tree_util.tree_leaves(cohort), key=lambda x: x.size)
    k = leaf.shape[0]
    q, scale = quantize_stacked(leaf, 8)
    quant = jax.jit(partial(ops.quantized_stacked_accumulate, mode="auto"))
    trimmed = jax.jit(partial(ops.trimmed_stacked_combine, mode="auto"))
    return {
        "auto": ops.default_quant_mode(),
        "quant_agg": "tpu_custom_call" in quant.lower(
            jnp.zeros(leaf.shape[1:], jnp.float32), q, scale).as_text(),
        "trimmed_agg": "tpu_custom_call" in trimmed.lower(
            leaf, jnp.full((k,), 1.0 / k, jnp.float32)).as_text(),
    }


def kernel_vs_oracle(cohort, weights) -> dict:
    """Each server aggregation on ``cohort`` through its kernel route
    ("auto") and through the jnp oracle of ``repro.kernels.ref``
    ("jnp"). Returns {kernel: max |kernel - oracle|}; raises past the
    tolerance."""
    trimmed = make_robust_aggregator(RUNS["trimmed_agg"]["aggregator"])
    reference = jax.tree.map(lambda x: x[0], cohort)
    aggregate = {
        "quant_agg": lambda mode: quantized_weighted_average(
            cohort, weights, RUNS["quant_agg"]["quant_bits"], mode=mode),
        "trimmed_agg": lambda mode: trimmed.aggregate(
            cohort, weights, reference, mode=mode)[0],
    }
    diffs = {}
    for name, agg in aggregate.items():
        got, want = agg("auto"), agg("jnp")
        diffs[name] = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_allclose(g, w, rtol=KERNEL_RTOL,
                                       atol=KERNEL_ATOL,
                                       err_msg=f"{name} vs its jnp oracle")
            diffs[name] = max(diffs[name], float(np.max(np.abs(g - w))))
    return diffs


def one_chip(seed: int) -> None:
    cfgs = sim_configs(seed)
    results, dataset = simulate(cfgs)
    for name, result in results.items():
        check_accuracy(name, result, cfgs[name].fl.max_rounds)

    cfg = cfgs["quant_agg"]
    cohort = trained_cohort(cfg, dataset)
    routes = kernel_routes(cohort)
    print(f"kernel routes: {routes}", flush=True)
    check(routes["auto"] == "pallas",
          f"'auto' resolved to {routes['auto']!r}, not the compiled kernel")
    for name in RUNS:
        check(routes[name], f"lowered {name} holds no tpu_custom_call")
    weights = np.full(cfg.fl.clients_per_round, float(cfg.n_per_client))
    diffs = kernel_vs_oracle(cohort, weights)
    print(f"kernel vs jnp oracle on one cohort of "
          f"{cfg.fl.clients_per_round}: max |diff| {diffs} "
          f"(rtol = atol = {KERNEL_RTOL})", flush=True)


# ---------------------------------------------------------------------------
# four chips: the LM trainer's tier-2 sync across pods
# ---------------------------------------------------------------------------


def named_leaves(params, names) -> dict:
    flat = {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(params)}
    return {n: np.asarray(flat[n], np.float32) for n in names}


def hfl_sync_check(argv) -> None:
    """Run the trainer's own loop (``train.run_hfl_rounds``) for one
    round, and compare each replica of ``SYNC_LEAVES`` after its sync with
    the host mean of the pre-sync replicas."""
    args = train.parse_args(argv)
    cfg = train.build_cfg(args)
    mesh = train.hfl_mesh(args.clusters)
    print(f"hfl {cfg.name}: {cfg.n_params() / 1e9:.2f} B params, mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}", flush=True)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=args.warmup)
    init, local, place_batch = train.hfl_programs(cfg, opt_cfg, mesh,
                                                  args.clusters)
    sync = train.hfl_sync(cfg, mesh, args.quant_bits)
    t0 = time.perf_counter()
    state = init(jax.random.PRNGKey(args.seed))
    embed = state.params["tok_embed"]
    print(f"state placed: tok_embed {embed.shape} over "
          f"{len(embed.sharding.device_set)} devices", flush=True)
    before = {}

    def checked_sync(state):
        before.update(named_leaves(state.params, SYNC_LEAVES))
        return sync(state)

    state, losses = train.run_hfl_rounds(
        state, local, checked_sync, place_batch,
        zip(*train.cluster_streams(cfg, args)), int(args.sync_every),
        args.steps, seed=args.seed)
    for i, loss in enumerate(losses):
        print(f"step {i} loss/cluster {loss.tolist()}", flush=True)
    print(f"{args.steps} steps, sync every {args.sync_every}: "
          f"{time.perf_counter() - t0:.1f} s wall", flush=True)
    check(bool(np.all(np.isfinite(losses))), f"loss is not finite: {losses}")
    after = named_leaves(state.params, SYNC_LEAVES)
    for name in SYNC_LEAVES:
        spread = float(np.max(np.abs(before[name][0] - before[name][1])))
        check(spread > 0.0, f"{name}: replicas were equal before the sync")
        mean = before[name].mean(axis=0)
        err = float(np.max(np.abs(after[name] - mean[None])))
        print(f"sync {name}: pre-sync replica spread {spread:.3e}, "
              f"max |replica - host mean| {err:.3e}", flush=True)
        for c in range(args.clusters):
            np.testing.assert_allclose(
                after[name][c], mean, rtol=SYNC_TOL, atol=SYNC_TOL,
                err_msg=f"{name} replica {c} vs host mean")


def main(argv=None) -> None:
    cache_dir = use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the LM trainer's sync check on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu()
    if args.four_chip:
        check(len(devices) == 4, f"--four-chip needs 4 devices, found "
                                 f"{len(devices)}")
    events = CacheEvents()
    jax.monitoring.register_event_listener(events)
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache: {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.four_chip:
        hfl_sync_check(HFL_ARGS + ["--seed", str(args.seed)])
    else:
        one_chip(args.seed)
    print(f"total {time.perf_counter() - t0:.1f} s wall; compile cache "
          f"{events.hits} hits, {events.writes} writes", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
