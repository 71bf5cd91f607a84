"""Resilience benchmark: FL accuracy / time-to-accuracy under faults.

The fault subsystem (``repro.sim.faults``) injects satellite outages,
per-contact transmission drops, radiation resets, silent payload
corruption, model poisoning, and the IWQoS'23 energy-drain attack into
the round engines. This sweep measures what each failure mode costs end
to end on the 5x10 constellation: accuracy and time-to-accuracy vs
outage rate, contact-drop rate, and attack intensity, plus the
retransmission overhead (re-billed bytes) the drop-retry policy pays —
and, for the payload faults, what the Byzantine-robust aggregation layer
(``FLConfig.aggregator``) buys back: accuracy collapses under the plain
weighted mean when corrupted/poisoned rows reach it, and recovers under
coordinate-wise trimmed mean / median / Krum.

Gates (exit nonzero on violation):
  * no-fault parity: the ``faults=None`` baseline is rerun through the
    retained pre-change engine (``repro.core.round_engine_ref``) and must
    be BITWISE identical — same round timings, same global params (the
    fault plumbing may not perturb the fault-free path);
  * zero-rate parity: a ``FaultConfig()`` that never fires (no outages,
    drops, or resets) must reproduce the ``faults=None`` baseline bitwise;
  * trace stability: the padded trainer compiles exactly once per sweep
    point no matter how many cohort slots the fault mask zeroes;
  * payload-fault accounting: corruption/poison columns must report
    ``corrupted_updates > 0`` (the injection actually fired);
  * defense recovery (full mode only — the smoke cohort of 2 is too
    narrow for rank defenses to bite): under corruption and under
    poisoning the plain-mean column must collapse below the no-fault
    baseline, and the best robust column must recover most of the gap.

Usage:
    PYTHONPATH=src python benchmarks/resilience.py \
        [--smoke] [--out BENCH_resilience.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import round_engine_ref as RER
from repro.core.client import clear_train_caches, train_cache_sizes
from repro.core.contact_plan import build_contact_plan
from repro.core.spaceify import FedAvgSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.sim.energy import EnergyConfig
from repro.sim.faults import EnergyDrainAttack, FaultConfig, PoisonAttack
from repro.sim.hardware import SMALLSAT_SBAND
from repro.launch.compile_cache import use_compile_cache

N_GS = 3
N_PER_CLIENT = 32
TARGET_ACC = 0.5
SEED = 0                             # fault-stream seed for every column
# the attack column: a small pack whose eclipse reserve the forced duty
# cycle can actually exhaust, and a 40% participation floor to pin under
ATK_BATTERY = EnergyConfig(battery_capacity_wh=2.0, initial_soc=1.0,
                           min_soc=0.4)


def _record_key(rec):
    return (rec.round, rec.t_start, rec.t_end, rec.duration_s, rec.idle_s,
            rec.comm_s, rec.train_s, rec.epochs, tuple(rec.participants),
            rec.accuracy, rec.skipped_faulted, rec.dropped_contacts,
            rec.retransmit_bytes)


def _bitwise_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _tta_h(recs, target: float):
    for r in recs:
        if r.accuracy >= target:
            return round((r.t_end - recs[0].t_start) / 3600, 3)
    return None


def sweep_columns(smoke: bool, n_sats: int):
    """(name, faults, energy, aggregator) columns: outage rate x drop
    rate x attack intensity x payload-fault defense, each varied against
    the same no-fault baseline."""
    atk = lambda duty: FaultConfig(seed=SEED, attack=EnergyDrainAttack(
        duty=duty, mode="training_tx"))
    # ~1 in 4 deliveries silently corrupted: far above any physical SEU
    # rate but — unlike 0.5 — still inside the defenses' breakdown
    # points (trim=0.2 tolerates 20% per end, median tolerates <1/2), so
    # the sweep shows the mean collapsing while the rank defenses hold
    corr = FaultConfig(corrupt_prob=0.25, seed=SEED)
    # every 5th satellite compromised (20% of the fleet), model
    # replacement at 5x amplification — one poisoned row per mean round
    # drags the global a full cohort-share backwards
    pois = FaultConfig(seed=SEED, poison=PoisonAttack(
        satellites=tuple(range(0, n_sats, 5)), scale=5.0))
    cols = [
        ("baseline", None, None, None),
        ("zero_rate", FaultConfig(seed=SEED), None, None),  # parity gate
        ("outage_6h", FaultConfig(mean_up_s=21_600.0, mean_down_s=1800.0,
                                  seed=SEED), None, None),
        ("outage_2h", FaultConfig(mean_up_s=7200.0, mean_down_s=1800.0,
                                  seed=SEED), None, None),
        ("drop_0.1", FaultConfig(drop_prob=0.1, seed=SEED), None, None),
        ("drop_0.3", FaultConfig(drop_prob=0.3, seed=SEED), None, None),
        ("battery_only", None, ATK_BATTERY, None),          # attack control
        ("attack_0.4", atk(0.4), ATK_BATTERY, None),
        ("attack_0.8", atk(0.8), ATK_BATTERY, None),
        # silent corruption: undefended mean vs the rank defenses
        ("corrupt_mean", corr, None, None),
        ("corrupt_trimmed", corr, None, "trimmed_mean"),
        ("corrupt_median", corr, None, "median"),
        # targeted poisoning: undefended mean vs median / Krum
        ("poison_mean", pois, None, None),
        ("poison_median", pois, None, "median"),
        ("poison_krum", pois, None, "krum"),
    ]
    if not smoke:
        cols.insert(6, ("combined", FaultConfig(
            mean_up_s=21_600.0, mean_down_s=1800.0, drop_prob=0.2,
            radiation_rate_per_day=2.0, seed=SEED), None, None))
    else:
        keep = {"baseline", "zero_rate", "outage_2h", "drop_0.3",
                "battery_only", "attack_0.8", "corrupt_mean",
                "corrupt_median", "poison_mean", "poison_median"}
        cols = [c for c in cols if c[0] in keep]
    return cols


def run_point(name, plan, ds, cfg):
    clear_train_caches()
    algo = FedAvgSat(plan, SMALLSAT_SBAND, ds, cfg)
    t0 = time.perf_counter()
    recs = algo.run()
    wall = time.perf_counter() - t0
    row = {
        "workload": name,
        "rounds": len(recs),
        "final_acc": round(recs[-1].accuracy, 4) if recs else 0.0,
        "best_acc": round(max((r.accuracy for r in recs), default=0.0), 4),
        "time_to_acc_h": _tta_h(recs, TARGET_ACC),
        "total_h": round((recs[-1].t_end - recs[0].t_start) / 3600, 3)
        if recs else None,
        "mean_round_h": round(float(np.mean(
            [r.duration_s for r in recs])) / 3600, 4) if recs else None,
        "skipped_faulted": int(sum(r.skipped_faulted for r in recs)),
        "dropped_contacts": int(sum(r.dropped_contacts for r in recs)),
        "retransmit_mb": round(sum(r.retransmit_bytes for r in recs)
                               / 1e6, 3),
        "skipped_low_power": int(sum(r.skipped_low_power for r in recs)),
        "energy_wh": round(sum(r.energy_wh for r in recs), 3),
        "corrupted_updates": int(sum(r.corrupted_updates for r in recs)),
        "clipped_updates": int(sum(r.clipped_updates for r in recs)),
        "wall_s": round(wall, 2),
        "traces": train_cache_sizes()["local_sgd_clients"],
    }
    return algo, recs, row


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_resilience.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smaller constellation, fewer columns")
    args = ap.parse_args()

    C, spc = (2, 3) if args.smoke else (5, 10)
    horizon_days = 0.5 if args.smoke else 1.0
    max_rounds = 3 if args.smoke else 12
    K = C * spc
    cfg_base = dict(model="mlp", clients_per_round=max(K // 5, 2), epochs=2,
                    batch_size=16, max_rounds=max_rounds, max_local_epochs=6,
                    lr=0.05)

    print(f"[resilience] fedavg on {C}x{spc}, {N_GS} GS, "
          f"{horizon_days:g} d horizon ({'smoke' if args.smoke else 'full'})")
    plan = build_contact_plan(C, spc, N_GS, horizon_s=horizon_days * 86_400,
                              dt_s=60.0)
    ds = make_federated_dataset("femnist", K, N_PER_CLIENT)

    rows, failures = [], []
    runs = {}
    for name, faults, energy, agg in sweep_columns(args.smoke, K):
        algo, recs, row = run_point(
            name, plan, ds, FLConfig(faults=faults, energy=energy,
                                     aggregator=agg, **cfg_base))
        row["aggregator"] = agg or "mean"
        rows.append(row)
        runs[name] = (recs, algo.global_params)
        if row["rounds"] and row["traces"] != 1:
            failures.append(f"{name}: trainer traced {row['traces']}x "
                            f"(fault masks must not retrace)")
        print(f"  {name:>15}: {row['rounds']} rounds, best_acc "
              f"{row['best_acc']}, tta {row['time_to_acc_h']} h, faulted "
              f"{row['skipped_faulted']}, drops {row['dropped_contacts']}, "
              f"rebill {row['retransmit_mb']} MB, low_power "
              f"{row['skipped_low_power']}, corrupted "
              f"{row['corrupted_updates']}, clipped "
              f"{row['clipped_updates']}")

    # gate 1 — no-fault parity vs the retained pre-change engine
    base_recs, base_params = runs["baseline"]
    clear_train_caches()
    ref = RER.FedAvgSatRef(plan, SMALLSAT_SBAND, ds, FLConfig(**cfg_base))
    ref_recs = ref.run()
    ref_ok = ([_record_key(r) for r in base_recs]
              == [_record_key(r) for r in ref_recs]) \
        and _bitwise_equal(base_params, ref.global_params)
    if not ref_ok:
        failures.append("faults=None baseline NOT bitwise-identical to "
                        "round_engine_ref (fault plumbing perturbed the "
                        "fault-free path)")
    print(f"  parity vs round_engine_ref: {'OK' if ref_ok else 'FAILED'}")

    # gate 2 — a never-firing FaultConfig must reproduce faults=None
    zr_recs, zr_params = runs["zero_rate"]
    zr_ok = ([_record_key(r) for r in base_recs]
             == [_record_key(r) for r in zr_recs]) \
        and _bitwise_equal(base_params, zr_params)
    if not zr_ok:
        failures.append("zero-rate FaultConfig NOT bitwise-identical to "
                        "faults=None")
    print(f"  zero-rate parity: {'OK' if zr_ok else 'FAILED'}")

    # gate 3 — payload-fault accounting: the injection must actually fire
    by = {r["workload"]: r for r in rows}
    for col in ("corrupt_mean", "corrupt_median", "poison_mean",
                "poison_median"):
        if col in by and by[col]["corrupted_updates"] == 0:
            failures.append(f"{col}: corrupted_updates == 0 (payload "
                            "faults never fired)")

    # gate 4 — defense recovery (full mode: the smoke cohort of 2 is too
    # narrow for a rank defense to reject anything). Collapse: the
    # undefended mean loses a chunk of the baseline's best accuracy.
    # Recovery: the best robust column wins most of it back.
    defense = {}
    if not args.smoke:
        base_best = by["baseline"]["best_acc"]
        for tag, mean_col, robust_cols in (
                ("corruption", "corrupt_mean",
                 ("corrupt_trimmed", "corrupt_median")),
                ("poison", "poison_mean",
                 ("poison_median", "poison_krum"))):
            mean_best = by[mean_col]["best_acc"]
            robust_best = max(by[c]["best_acc"] for c in robust_cols)
            collapsed = mean_best <= base_best - 0.05
            recovered = robust_best >= mean_best + 0.05
            defense[tag] = {"baseline": base_best, "mean": mean_best,
                            "robust": robust_best, "collapsed": collapsed,
                            "recovered": recovered}
            if not collapsed:
                failures.append(
                    f"{tag}: plain mean did not collapse (best_acc "
                    f"{mean_best} vs baseline {base_best}) — injection "
                    "too weak to demonstrate the defense")
            if not recovered:
                failures.append(
                    f"{tag}: robust aggregation did not recover (best "
                    f"robust {robust_best} vs mean {mean_best})")
            print(f"  {tag} defense: baseline {base_best}, mean "
                  f"{mean_best}, robust {robust_best} "
                  f"({'OK' if collapsed and recovered else 'FAILED'})")

    out = {
        "benchmark": "resilience",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "scale": {"clusters": C, "sats_per_cluster": spc,
                  "ground_stations": N_GS, "horizon_days": horizon_days,
                  "n_per_client": N_PER_CLIENT, "max_rounds": max_rounds},
        "target_accuracy": TARGET_ACC,
        "fault_seed": SEED,
        "attack": {"battery_capacity_wh": ATK_BATTERY.battery_capacity_wh,
                   "min_soc": ATK_BATTERY.min_soc, "mode": "training_tx"},
        "sweep": rows,
        "parity": {"vs_round_engine_ref": ref_ok, "zero_rate": zr_ok},
        "defense": defense,
        "failures": failures,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all resilience parity + trace gates passed")


if __name__ == "__main__":
    main()
