"""Heterogeneous-fleet benchmark: FLyCube / S-band mix ratio vs
time-to-accuracy (the ROADMAP heterogeneous-fleet sweep).

The paper's design space (§4.1.2, Table 2, Fig. 9) spans FLyCube LoRa
radios (1.6 KB/s) to S-band smallsats (MB/s); real constellations mix
them. The round engine now times every satellite with its own
``HardwareProfile`` (``repro.sim.hardware.FleetProfile``), so this sweep
replaces a growing fraction of an S-band constellation with FLyCube
LoRa satellites and measures what the slow radios cost end to end:
rounds get gated by the slowest selected radio, so mean round duration —
and with it time-to-accuracy — grows with the LoRa fraction.

Gates (exit nonzero on violation):
  * uniform-fleet parity: the all-S-band (ratio 0.0) and all-FLyCube
    (ratio 1.0) sweep points are rerun through the scalar
    primary-profile engine and must be BITWISE identical — same round
    records, same global params (a uniform ``FleetProfile`` evaluates
    the exact same IEEE arithmetic as the scalar path);
  * trace stability: the padded trainer still compiles exactly once per
    sweep point no matter the fleet mix.

Usage:
    PYTHONPATH=src python benchmarks/fleet_mix_perf.py \
        [--smoke] [--out BENCH_fleet_mix.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.client import clear_train_caches, train_cache_sizes
from repro.core.contact_plan import build_contact_plan
from repro.core.spaceify import FedAvgSat, FedProxSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.sim.hardware import FLYCUBE, SMALLSAT_SBAND, FleetProfile
from repro.launch.compile_cache import use_compile_cache

ALGOS = {"fedavg": FedAvgSat, "fedprox": FedProxSat}
C, SPC = 2, 5                       # the paper's 2x5 constellation
K = C * SPC
N_GS = 3
N_PER_CLIENT = 32
TARGET_ACC = 0.5                    # time-to-accuracy target


def mixed(ratio: float) -> FleetProfile:
    """First ``round(ratio*K)`` satellites fly FLyCube LoRa radios, the
    rest are S-band smallsats."""
    n_fly = int(round(ratio * K))
    return FleetProfile.from_profiles(
        [FLYCUBE if k < n_fly else SMALLSAT_SBAND for k in range(K)])


def _cfg(max_rounds: int) -> FLConfig:
    return FLConfig(model="mlp", clients_per_round=K // 2, epochs=2,
                    batch_size=16, max_rounds=max_rounds,
                    max_local_epochs=8, lr=0.05)


def _record_key(rec):
    return (rec.round, rec.t_start, rec.t_end, rec.duration_s, rec.idle_s,
            rec.comm_s, rec.train_s, rec.epochs, tuple(rec.participants),
            rec.accuracy)


def _bitwise_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _tta_h(recs, target: float):
    for r in recs:
        if r.accuracy >= target:
            return round((r.t_end - recs[0].t_start) / 3600, 3)
    return None


def run_sweep_point(name, cls, plan, ds, cfg, fleet):
    clear_train_caches()
    algo = cls(plan, fleet, ds, cfg)
    t0 = time.perf_counter()
    recs = algo.run()
    wall = time.perf_counter() - t0
    traces = train_cache_sizes()["local_sgd_clients"]
    row = {
        "workload": name,
        "rounds": len(recs),
        "final_acc": round(recs[-1].accuracy, 4) if recs else 0.0,
        "best_acc": round(max((r.accuracy for r in recs), default=0.0), 4),
        "mean_round_h": round(float(np.mean(
            [r.duration_s for r in recs])) / 3600, 4) if recs else None,
        "mean_comm_s": round(float(np.mean(
            [r.comm_s for r in recs])), 3) if recs else None,
        "mean_idle_h": round(float(np.mean(
            [r.idle_s for r in recs])) / 3600, 4) if recs else None,
        "total_h": round((recs[-1].t_end - recs[0].t_start) / 3600, 3)
        if recs else None,
        "time_to_acc_h": _tta_h(recs, TARGET_ACC),
        "wall_s": round(wall, 2),
        "traces": traces,
    }
    return algo, recs, row


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet_mix.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewer ratios and rounds")
    args = ap.parse_args()

    ratios = [0.0, 0.5, 1.0] if args.smoke \
        else [0.0, 0.2, 0.4, 0.6, 0.8, 1.0]
    max_rounds = 4 if args.smoke else 24
    horizon_days = 0.5 if args.smoke else 1.5
    algorithms = ["fedavg"] if args.smoke else ["fedavg", "fedprox"]

    plan = build_contact_plan(C, SPC, N_GS, horizon_s=horizon_days * 86400,
                              dt_s=60.0)
    ds = make_federated_dataset("femnist", K, N_PER_CLIENT)

    rows, failures = [], []
    uniform_runs = {}                 # (algo, ratio) -> (recs, params)
    for alg in algorithms:
        print(f"[{alg}] FLyCube mix-ratio sweep "
              f"({C}x{SPC}, {N_GS} GS, {horizon_days:g} d)")
        for ratio in ratios:
            name = f"{alg}_mix{ratio:.1f}"
            algo, recs, row = run_sweep_point(
                name, ALGOS[alg], plan, ds, _cfg(max_rounds), mixed(ratio))
            row.update({"algorithm": alg, "mix_ratio": ratio,
                        "n_flycube": int(round(ratio * K))})
            rows.append(row)
            if row["traces"] > 1:
                failures.append(f"{name}: trainer traced {row['traces']}x "
                                f"(fleet mix must not retrace)")
            if ratio in (0.0, 1.0):
                uniform_runs[(alg, ratio)] = (recs, algo.global_params)
            print(f"  ratio {ratio:.1f}: {row['rounds']} rounds, "
                  f"best_acc {row['best_acc']}, mean_round "
                  f"{row['mean_round_h']} h, comm {row['mean_comm_s']} s, "
                  f"tta {row['time_to_acc_h']} h")

    # uniform-fleet parity gate: the fleet engine at ratio 0/1 must be
    # bitwise-identical to the scalar primary-profile engine
    parity = {}
    for (alg, ratio), (recs, params) in uniform_runs.items():
        hw = SMALLSAT_SBAND if ratio == 0.0 else FLYCUBE
        clear_train_caches()
        ref = ALGOS[alg](plan, hw, ds, _cfg(max_rounds))
        ref_recs = ref.run()
        ok = ([_record_key(r) for r in recs] ==
              [_record_key(r) for r in ref_recs]) \
            and _bitwise_equal(params, ref.global_params)
        parity[f"{alg}_uniform_{hw.name}"] = ok
        if not ok:
            failures.append(f"{alg} ratio {ratio}: uniform fleet NOT "
                            f"bitwise-identical to the {hw.name} scalar "
                            f"engine")
        print(f"  parity {alg} vs scalar {hw.name}: "
              f"{'OK' if ok else 'FAILED'}")

    out = {
        "benchmark": "fleet_mix_perf",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "scale": {"clusters": C, "sats_per_cluster": SPC,
                  "ground_stations": N_GS, "horizon_days": horizon_days,
                  "n_per_client": N_PER_CLIENT},
        "target_accuracy": TARGET_ACC,
        "profiles": {"flycube_isl_bps": FLYCUBE.isl_rate_bps,
                     "flycube_down_bps": FLYCUBE.downlink_rate_bps,
                     "sband_down_bps": SMALLSAT_SBAND.downlink_rate_bps},
        "sweep": rows,
        "uniform_parity": parity,
        "failures": failures,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all fleet-mix parity + trace gates passed")


if __name__ == "__main__":
    main()
