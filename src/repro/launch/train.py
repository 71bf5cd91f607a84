"""End-to-end LM training driver (deliverable b).

Modes:
  * plain:   synchronous data-parallel training of any --arch (reduced or
             full config) on synthetic bigram token streams;
  * hfl:     the paper's AutoFLSat hierarchical mode — per-cluster replicas,
             H local steps between cluster syncs (H fixed or derived from a
             simulated constellation's ISL schedule), optional QuAFL-
             quantized sync.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b --reduced \
      --hfl --clusters 2 --sync-every orbit --steps 60
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro import obs
from repro.checkpoint import save_pytree
from repro.configs import get_config, get_smoke_config
from repro.core import hierarchy as H
from repro.data.tokens import synthetic_lm_batches
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim.optimizers import AdamWConfig
from repro.sharding.partition import named
from repro.train import steps as ST


def build_cfg(args):
    cfg = get_smoke_config(args.arch) if args.reduced else get_config(args.arch)
    over = {"compute_dtype": args.dtype}
    if args.vocab:
        over["vocab"] = args.vocab
    return dataclasses.replace(cfg, **over)


def hfl_mesh(n_clusters: int):
    """(pod, data, model) mesh over every device: one pod per cluster
    where the clusters divide the devices, the rest of each pod on
    ``model``. On one device it is (1, 1, 1) and nothing is sharded."""
    n_dev = jax.device_count()
    pod = n_clusters if n_dev % n_clusters == 0 else 1
    return make_local_mesh(data=1, model=n_dev // pod, pod=pod)


def hfl_programs(cfg, opt_cfg, mesh, n_clusters: int):
    """The hierarchical trainer's tier-1 programs on ``mesh``:
    ``(init, local, place_batch)``. ``init(key)`` creates the per-cluster
    state already sharded by ``hfl_state_specs``; ``local`` keeps it in
    that sharding and donates its input; ``place_batch(batches)`` stacks
    one host batch per cluster into (C, local_b, ...) and places it on
    the clusters' pods."""
    state_sh = named(mesh, H.hfl_state_specs(cfg, mesh))
    init = jax.jit(lambda key: H.init_hfl_state(key, cfg, n_clusters),
                   out_shardings=state_sh)
    local = jax.jit(H.make_hfl_local_step(cfg, opt_cfg),
                    in_shardings=(state_sh, None),
                    out_shardings=(state_sh, None), donate_argnums=0)

    def place_batch(batches):
        hb = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        return jax.device_put(
            hb, named(mesh, H.hfl_batch_specs(cfg, mesh, hb)))

    return init, local, place_batch


def hfl_sync(cfg, mesh, quant_bits: int = 0, cluster_weights=None):
    """The tier-2 cluster sync, jitted to keep (and donate) the state in
    its ``hfl_state_specs`` sharding on ``mesh``."""
    state_sh = named(mesh, H.hfl_state_specs(cfg, mesh))
    return jax.jit(H.make_cluster_sync(cfg, quant_bits=quant_bits,
                                       cluster_weights=cluster_weights),
                   in_shardings=(state_sh,), out_shardings=state_sh,
                   donate_argnums=0)


def run_hfl_rounds(state, local, sync, place_batch, batches, h_sync: int,
                   steps: int, seed=None):
    """The hierarchical trainer's loop: ``steps`` tier-1 steps in tier-2
    rounds of ``h_sync`` steps, each round closed by one ``sync``. A step
    is ``local(state, place_batch(next(batches)))``, where ``batches``
    yields one host batch per cluster; a last round shorter than
    ``h_sync`` ends without a sync. Returns the new state and the
    per-cluster loss of every step, (steps, C) on the host, read once a
    round through ``obs.sync``. The call is recorded as one
    ``obs.RunTrace`` of algorithm ``"hfl"`` under ``seed``."""
    losses = []
    with obs.logged_run(obs.RunTrace(seed, "hfl"), "hfl.run") as trace:
        for start in range(0, steps, h_sync):
            n = min(h_sync, steps - start)
            with obs.span("hfl.round"):
                metrics = []
                for _ in range(n):
                    cluster_batches = next(batches)
                    with obs.span("hfl.place_batch"):
                        batch = place_batch(cluster_batches)
                    with obs.span("hfl.local"):
                        state, m = local(state, batch)
                    metrics.append(m["loss"])
                    obs.count("hfl_steps")
                    obs.count("tokens", sum(int(np.size(b["tokens"]))
                                            for b in cluster_batches))
                if n == h_sync:
                    with obs.span("hfl.sync"):
                        state = sync(state)
                    obs.count("hfl_syncs")
                losses += obs.sync(metrics)
            if n == h_sync:
                trace.round_done()
    return state, np.asarray(losses, np.float32)


def cluster_streams(cfg, args):
    """One synthetic token stream per cluster, each from its own seed, so
    every cluster sees its own (non-IID) data."""
    return [synthetic_lm_batches(cfg.vocab, args.batch, args.seq, args.steps,
                                 seed=args.seed + 17 * c)
            for c in range(args.clusters)]


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    # hierarchical (AutoFLSat) mode
    ap.add_argument("--hfl", action="store_true")
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--sync-every", default="8",
                    help="steps between cluster syncs, or 'orbit' to derive "
                         "from a simulated constellation's ISL schedule")
    ap.add_argument("--quant-bits", type=int, default=0)
    ap.add_argument("--fleet", default="smallsat_sband",
                    help="with --hfl --sync-every orbit: comma-separated "
                         "hardware profiles (flycube | smallsat_sband) "
                         "cycled over the simulated constellation; a mixed "
                         "fleet bottlenecks the ISL schedule on its "
                         "slowest radio")
    ap.add_argument("--power-check", action="store_true",
                    help="with --hfl --sync-every orbit: report whether the "
                         "derived schedule's duty cycle fits the eclipse-"
                         "aware power budget of the simulated constellation")
    ap.add_argument("--policy", default="",
                    help="with --hfl --sync-every orbit: selection policy "
                         "(repro.core.policy name, e.g. deadline_aware) — "
                         "derives per-member tier-1 step budgets over the "
                         "simulated fleet and weights the tier-2 cluster "
                         "sync accordingly; empty keeps the uniform "
                         "(bitwise pre-policy) sync")
    return ap.parse_args(argv)


def main():
    use_compile_cache()
    args = parse_args()
    cfg = build_cfg(args)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=args.warmup)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.time()

    if args.hfl:
        nc = args.clusters
        mesh = hfl_mesh(nc)
        cluster_w = None
        if args.policy and args.sync_every != "orbit":
            raise SystemExit("--policy needs --hfl --sync-every orbit (the "
                             "policy budgets are derived from the simulated "
                             "fleet and ISL schedule)")
        init, local, place_batch = hfl_programs(cfg, opt_cfg, mesh, nc)
        state = init(key)
        if args.sync_every == "orbit":
            from repro.core.contact_plan import build_contact_plan
            from repro.core.quantize import transmit_bytes
            from repro.sim.hardware import (FLYCUBE, SMALLSAT_SBAND,
                                            FleetProfile)
            named = {"flycube": FLYCUBE, "smallsat_sband": SMALLSAT_SBAND}
            try:
                cycle = [named[n.strip()]
                         for n in args.fleet.split(",") if n.strip()]
            except KeyError as e:
                raise SystemExit(f"unknown --fleet profile {e}; choose "
                                 f"from {sorted(named)}")
            if not cycle:
                raise SystemExit(f"--fleet needs at least one profile "
                                 f"from {sorted(named)}")
            spc = 10
            plan = build_contact_plan(nc, spc, 3, horizon_s=86400.0,
                                      dt_s=60.0, with_isl_pairs=True)
            fleet = FleetProfile.from_profiles(
                [cycle[i % len(cycle)] for i in range(nc * spc)])
            # bill the ISL exchange at the same (possibly quantized) wire
            # size as every other link so the schedule stays consistent;
            # a mixed fleet's exchange is gated by its slowest ISL radio
            h_sync = H.sync_interval_from_orbits(
                plan, fleet,
                transmit_bytes(state.params, args.quant_bits) / nc,
                step_time_s=1.0)
            print(f"[hfl] ISL schedule ({args.fleet}) => sync every "
                  f"H={h_sync} steps")
            if args.policy:
                w = H.policy_cluster_weights(plan, fleet, args.policy,
                                             epochs=h_sync)
                if not np.allclose(w, 1.0):
                    cluster_w = w
                print(f"[hfl] policy '{args.policy}': tier-2 cluster "
                      f"weights = {[round(float(x), 3) for x in w]}"
                      + ("" if cluster_w is not None
                         else " (uniform => exact unweighted sync)"))
            if args.power_check:
                from repro.orbit.eclipse import mean_eclipse_fraction
                from repro.sim.hardware import oap_added_mw, power_feasible
                ecl = mean_eclipse_fraction(plan.constellation)
                # each satellite class pays its own duty cycle: check the
                # schedule against every distinct profile in the fleet
                for hw in dict.fromkeys(fleet.profiles):
                    tx_s = float(hw.tx_time(
                        transmit_bytes(state.params, args.quant_bits) / nc,
                        "isl"))
                    duty_tx = min(tx_s / max(h_sync * 1.0, 1e-9), 1.0)
                    duty = {"training": 1.0 - duty_tx,
                            "training_tx": duty_tx}
                    oap = oap_added_mw(duty, hw.power)
                    # solar input flows only outside eclipse; idle always on
                    budget = hw.power_generation_mw * (1.0 - ecl) \
                        - hw.power.idle
                    ok = power_feasible(duty, hw, eclipse_fraction=ecl)
                    verdict = "OK" if ok else \
                        "OVER BUDGET (expect SoC-gated stalls)"
                    print(f"[hfl] power check [{hw.name}]: eclipse "
                          f"{ecl:.1%}, schedule adds {oap:.0f} mW vs "
                          f"{budget:.0f} mW sunlit-average margin => "
                          f"{verdict}")
        else:
            h_sync = int(args.sync_every)
        sync = hfl_sync(cfg, mesh, args.quant_bits, cluster_w)
        state, losses = run_hfl_rounds(
            state, local, sync, place_batch,
            zip(*cluster_streams(cfg, args)), h_sync, args.steps,
            seed=args.seed)
        for i, loss in enumerate(losses):
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss/cluster="
                      f"{[round(float(x), 4) for x in loss]} "
                      f"({time.time() - t0:.1f}s)", flush=True)
        final_loss = float(losses[-1].mean())
    else:
        state = ST.init_train_state(key, cfg)
        step = jax.jit(ST.make_train_step(cfg, opt_cfg), donate_argnums=0)
        stream = synthetic_lm_batches(cfg.vocab, args.batch, args.seq,
                                      args.steps, seed=args.seed)
        for i, batch in enumerate(stream):
            state, m = step(state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(m['loss']):.4f} "
                      f"gnorm={float(m['grad_norm']):.3f} "
                      f"({time.time() - t0:.1f}s)", flush=True)
        final_loss = float(m["loss"])

    if args.checkpoint:
        save_pytree(args.checkpoint, state.params,
                    extra_meta={"steps": args.steps})
        print(f"checkpoint -> {args.checkpoint}")
    print(json.dumps({"arch": cfg.name, "steps": args.steps,
                      "final_loss": round(final_loss, 4),
                      "wall_s": round(time.time() - t0, 1)}))


if __name__ == "__main__":
    main()
