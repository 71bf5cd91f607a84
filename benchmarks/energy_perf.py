"""Energy-engine benchmark: packed eclipse intervals + event-driven SoC
advancement vs the retained per-timestep reference integrator
(``repro.sim.energy_ref``) at small (5x5), paper (10x10), and
mega-constellation (40x40, dt=10s) scale, emitting ``BENCH_energy.json``
so the speedup is tracked across PRs.

Usage:
    PYTHONPATH=src python benchmarks/energy_perf.py [--scales small paper mega]
        [--out BENCH_energy.json] [--smoke]

Three metered workloads per scale, each parity-checked in-run against the
reference engine before it is timed:

  * build    — eclipse geometry into each engine's resident form: the
               dense (T, K) float64 sunlit matrix (reference) vs packed
               terminator-crossing intervals (``eclipse_series(packed=
               True)``); the memory ratio is the O(T*K) -> O(K*W) claim.
  * advance  — the round engine's gating sequence at a 30-minute round
               cadence over 24 h: ``advance_to`` (whole fleet), the
               ``eligible()`` mask, and participant billing per round.
               A denser 10-minute cadence is reported alongside (the
               reference walks every grid cell regardless of cadence;
               the interval engine's cost scales with queries + events).
  * recover  — batched ``recover_times`` over the whole drained fleet vs
               the reference's per-satellite per-cell Python scan.

The CLI exits nonzero if the mega-scale round-cadence fleet-advancement
speedup drops below the 10x target (matching contact_plan_perf.py).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.orbit.constellation import WalkerStar, satellite_elements
from repro.orbit.eclipse import eclipse_series
from repro.sim.energy import EnergyConfig, EnergySim
from repro.sim.energy_ref import EnergySimRef
from repro.sim.hardware import FLYCUBE
from repro.launch.compile_cache import use_compile_cache

SCALES = {
    # name: (clusters, sats/cluster, horizon_s, eclipse_dt_s)
    "small": (5, 5, 86_400.0, 60.0),
    "paper": (10, 10, 86_400.0, 30.0),
    "mega": (40, 40, 86_400.0, 10.0),
}

ROUND_CADENCE_S = 1_800.0      # gated workload: one FL round per 30 min
DENSE_CADENCE_S = 600.0        # secondary row: 10-min cadence
PARTICIPANTS = 10
TRAIN_S, COMM_S = 600.0, 30.0
SPEEDUP_TARGET = 10.0


def _timeit(fn, repeat=3):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _round_workload(sim, query_ts, parts, train_s, comm_s):
    """The FL-gating sequence: advance the fleet, read the eligibility
    mask, bill the round's participants."""
    for i, t in enumerate(query_ts):
        sim.advance_to(float(t))
        sim.eligible()
        sim.bill_activity(parts[i], train_s, comm_s)
    return sim


def bench_scale(name: str, smoke: bool) -> dict:
    nc, spc, horizon, dt = SCALES[name]
    if smoke:
        horizon = min(horizon, 21_600.0)
    c = WalkerStar(nc, spc)
    raan, phase, _ = satellite_elements(c)
    times = np.arange(0.0, horizon, dt)
    incl = np.radians(c.inclination_deg)
    profiles = (FLYCUBE,) * c.n_sats
    cfg = EnergyConfig(battery_capacity_wh=10.0, initial_soc=0.6,
                       min_soc=0.5, eclipse_dt_s=dt)

    # -- build: dense series (reference resident form) vs packed intervals
    t0 = time.perf_counter()
    dense = eclipse_series(c, raan, phase, incl, times)
    t_build_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    packed = eclipse_series(c, raan, phase, incl, times, packed=True)
    t_build_packed = time.perf_counter() - t0
    assert (packed.to_dense(times) == dense).all(), \
        "packed eclipse parity failure"
    dense_bytes = dense.shape[0] * dense.shape[1] * 8   # ref's float64 form
    mem_ratio = dense_bytes / max(packed.nbytes, 1)

    rng = np.random.default_rng(0)
    rows = {}
    for label, cadence in (("round", ROUND_CADENCE_S),
                           ("dense", DENSE_CADENCE_S)):
        q = max(int(horizon // cadence), 2)
        query_ts = np.linspace(horizon / q, horizon * 1.02, q)  # + past-grid
        parts = [rng.integers(0, c.n_sats, PARTICIPANTS) for _ in range(q)]
        train_s = np.full(PARTICIPANTS, TRAIN_S)
        comm_s = np.full(PARTICIPANTS, COMM_S)

        t_new, sim_new = _timeit(lambda: _round_workload(
            EnergySim(times, packed, profiles, cfg),
            query_ts, parts, train_s, comm_s), repeat=1 if smoke else 3)
        t_ref, sim_ref = _timeit(lambda: _round_workload(
            EnergySimRef(times, dense, profiles, cfg),
            query_ts, parts, train_s, comm_s), repeat=1 if smoke else 2)
        assert np.allclose(sim_new.soc_wh, sim_ref.soc_wh, atol=1e-6), \
            f"advancement parity failure ({label})"
        rows[label] = (q, t_ref, t_new)

    # -- recover: drained fleet, batched vs per-satellite scan
    drained = EnergyConfig(battery_capacity_wh=10.0, initial_soc=0.1,
                           min_soc=0.5, eclipse_dt_s=dt)
    sim_new = EnergySim(times, packed, profiles, drained)
    sim_ref = EnergySimRef(times, dense, profiles, drained)
    ks = np.arange(c.n_sats)
    t_rec_new, rec_new = _timeit(lambda: sim_new.recover_times(ks),
                                 repeat=1 if smoke else 3)
    t_rec_ref, rec_ref = _timeit(
        lambda: [sim_ref.recover_time(int(k)) for k in ks], repeat=1)
    rec_ref = np.array([np.inf if r is None else r for r in rec_ref])
    both = np.isfinite(rec_new) == np.isfinite(rec_ref)
    assert both.all() and np.allclose(
        np.where(np.isfinite(rec_new), rec_new, 0.0),
        np.where(np.isfinite(rec_ref), rec_ref, 0.0), atol=1e-4), \
        "recover parity failure"

    q_round, t_ref, t_new = rows["round"]
    q_dense, t_dref, t_dnew = rows["dense"]
    return {
        "clusters": nc, "sats_per_cluster": spc, "n_sats": c.n_sats,
        "horizon_s": horizon, "eclipse_dt_s": dt, "grid_cells": len(times),
        "n_transitions": len(packed.trans_t),
        "build_reference_s": round(t_build_ref, 4),
        "build_packed_s": round(t_build_packed, 4),
        "dense_sunlit_bytes": dense_bytes,
        "packed_bytes": packed.nbytes,
        "memory_ratio": round(mem_ratio, 1),
        "rounds": q_round,
        "advance_reference_s": round(t_ref, 5),
        "advance_vectorized_s": round(t_new, 5),
        "advance_speedup": round(t_ref / max(t_new, 1e-9), 1),
        "dense_cadence_rounds": q_dense,
        "dense_cadence_speedup": round(t_dref / max(t_dnew, 1e-9), 1),
        "recover_reference_s": round(t_rec_ref, 5),
        "recover_vectorized_s": round(t_rec_new, 5),
        "recover_speedup": round(t_rec_ref / max(t_rec_new, 1e-9), 1),
        "parity": True,
    }


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scales", nargs="+", default=None,
                    choices=list(SCALES))
    ap.add_argument("--smoke", action="store_true",
                    help="small scale, short horizon, single repeats, "
                         "no speedup gate (CI)")
    ap.add_argument("--out", default="BENCH_energy.json")
    args = ap.parse_args()
    scales = args.scales or (["small"] if args.smoke else list(SCALES))

    results = {}
    for name in scales:
        print(f"== {name}: {SCALES[name]}", flush=True)
        row = bench_scale(name, args.smoke)
        results[name] = row
        print(f"   {row['n_sats']} sats, {row['grid_cells']} cells -> "
              f"{row['n_transitions']} transitions | "
              f"mem {row['dense_sunlit_bytes'] / 1e6:.1f}MB -> "
              f"{row['packed_bytes'] / 1e3:.1f}KB ({row['memory_ratio']}x) | "
              f"advance {row['advance_reference_s']:.3f}s -> "
              f"{row['advance_vectorized_s']:.3f}s "
              f"({row['advance_speedup']}x; dense cadence "
              f"{row['dense_cadence_speedup']}x) | "
              f"recover {row['recover_reference_s']:.3f}s -> "
              f"{row['recover_vectorized_s']:.4f}s "
              f"({row['recover_speedup']}x)", flush=True)

    out = Path(args.out)
    out.write_text(json.dumps({"benchmark": "energy_perf",
                               "results": results}, indent=2) + "\n")
    print(f"wrote {out}")
    if not args.smoke and "mega" in results:
        if results["mega"]["advance_speedup"] < SPEEDUP_TARGET:
            raise SystemExit("mega fleet-advancement speedup below the "
                             f"{SPEEDUP_TARGET:g}x target")
        if results["mega"]["memory_ratio"] < SPEEDUP_TARGET:
            raise SystemExit("mega packed-eclipse memory ratio below the "
                             f"{SPEEDUP_TARGET:g}x target")


if __name__ == "__main__":
    main()
