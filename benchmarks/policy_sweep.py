"""Selection-policy benchmark: the policy layer vs the built-in rules.

Two scenarios over the FedAvg round engine:

  * storm/deadline — the BENCH_degradation storm (a correlated storm
    pinning all but one plane's links to the floor for most of a day)
    under deadline/quorum rounds. ``scheduled`` keeps picking the
    earliest-return cohort and walks straight into the storm;
    ``deadline_aware`` penalizes candidates whose projected return
    crosses a storm footprint or the round deadline, so the cohort
    shifts to the clean plane and convergence keeps the fair-weather
    cadence;
  * tight energy — a small battery pack under eclipse. The binary SoC
    floor (``EnergyConfig.min_soc``) masks drained satellites outright
    and happily trains the rest through eclipse; ``energy_aware``
    replaces the floor with soft SoC-weighted scoring plus a
    sunlit-arc deferral, spending the fleet's watt-hours where the
    sun is.

Gates (exit nonzero on violation):
  * built-in parity: an explicit ``policy="scheduled"`` run must be
    BITWISE identical (records and global params) to the ``policy=None``
    built-in — the policy layer may not perturb the legacy path;
  * single trace: every column must compile the client trainer exactly
    once (the policy layer may not retrace the fixed-shape dispatch);
  * storm accounting: ``deadline_aware`` must actually demote
    storm-exposed candidates (``policy_skips["storm_exposed"] > 0``);
  * energy accounting: ``energy_aware`` must actually defer eclipsed
    low-SoC candidates (``policy_skips["eclipse_deferred"] > 0``);
  * time-to-accuracy (full mode only — the smoke cohort is too small
    for a stable TTA): ``scheduled``'s TTA through the storm must be
    >= 1.2x ``deadline_aware``'s (or never reach the target);
  * Wh-to-accuracy (full mode only): ``energy_aware`` must reach the
    target accuracy on no more fleet energy than the binary floor
    (or the floor must fail to reach it at all).

Usage:
    PYTHONPATH=src python benchmarks/policy_sweep.py \
        [--smoke] [--out BENCH_policy.json]
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
from repro.core.spaceify import EnergyConfig, FedAvgSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.sim.faults import FaultConfig, StormConfig, StormEvent
from repro.sim.hardware import SMALLSAT_SBAND
from repro.launch.compile_cache import use_compile_cache

N_GS = 3
N_PER_CLIENT = 32
TARGET_ACC = 0.7
SEED = 0


def _record_key(rec):
    return (rec.round, rec.t_start, rec.t_end, rec.duration_s, rec.idle_s,
            rec.comm_s, rec.train_s, rec.epochs, tuple(rec.participants),
            rec.accuracy, rec.skipped_low_power, rec.skipped_faulted,
            rec.dropped_contacts, rec.deadline_expired,
            rec.stragglers_carried, rec.retries_exhausted, rec.storm_events,
            rec.policy_deferred, tuple(sorted(rec.policy_skips.items())))


def _bitwise_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _tta_h(recs, target: float):
    for r in recs:
        if r.accuracy >= target:
            return round((r.t_end - recs[0].t_start) / 3600, 3)
    return None


def _wh_to_acc(recs, target: float):
    """Fleet energy spent up to (and including) the round that first
    reaches ``target`` accuracy; None if the run never gets there."""
    spent = 0.0
    for r in recs:
        spent += r.energy_wh
        if r.accuracy >= target:
            return round(spent, 3)
    return None


def storm_faults(n_clusters: int, t_start_s: float, duration_s: float,
                 drop_prob: float):
    """The BENCH_degradation storm: every plane but the last has its
    transmission attempts dropped with high probability while it rages
    (no outages — the satellites are up, their links are dead)."""
    events = tuple(StormEvent(t_start=t_start_s, duration_s=duration_s,
                              cluster=c, severity=1.0)
                   for c in range(max(n_clusters - 1, 1)))
    return FaultConfig(seed=SEED, storms=StormConfig(
        events=events, outage_prob=0.0, drop_prob=drop_prob))


def run_point(name, plan, ds, cfg):
    clear_train_caches()
    algo = FedAvgSat(plan, SMALLSAT_SBAND, ds, cfg)
    t0 = time.perf_counter()
    recs = algo.run()
    wall = time.perf_counter() - t0
    skips = {}
    for r in recs:
        for reason, n in r.policy_skips.items():
            skips[reason] = skips.get(reason, 0) + int(n)
    row = {
        "workload": name,
        "policy": cfg.policy if isinstance(cfg.policy, str) else
        ("builtin" if cfg.policy is None else type(cfg.policy).__name__),
        "rounds": len(recs),
        "final_acc": round(recs[-1].accuracy, 4) if recs else 0.0,
        "best_acc": round(max((r.accuracy for r in recs), default=0.0), 4),
        "time_to_acc_h": _tta_h(recs, TARGET_ACC),
        "total_h": round((recs[-1].t_end - recs[0].t_start) / 3600, 3)
        if recs else None,
        "energy_wh": round(sum(r.energy_wh for r in recs), 3),
        "wh_to_acc": _wh_to_acc(recs, TARGET_ACC),
        "skipped_low_power": int(sum(r.skipped_low_power for r in recs)),
        "deadline_expired": int(sum(r.deadline_expired for r in recs)),
        "stragglers_carried": int(sum(r.stragglers_carried for r in recs)),
        "retries_exhausted": int(sum(r.retries_exhausted for r in recs)),
        "dropped_contacts": int(sum(r.dropped_contacts for r in recs)),
        "policy_deferred": int(sum(r.policy_deferred for r in recs)),
        "policy_skips": skips,
        "wall_s": round(wall, 2),
        "traces": train_cache_sizes()["local_sgd_clients"],
    }
    return algo, recs, row


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_policy.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smaller constellation, fewer rounds")
    args = ap.parse_args()

    rows, failures, runs = [], [], {}

    def gate_rows(plan, ds, cols):
        for name, cfg in cols:
            algo, recs, row = run_point(name, plan, ds, cfg)
            rows.append(row)
            runs[name] = (recs, algo.global_params)
            if row["rounds"] and row["traces"] != 1:
                failures.append(f"{name}: trainer traced {row['traces']}x")
            print(f"  {name:>16}: {row['rounds']} rounds, best_acc "
                  f"{row['best_acc']}, tta {row['time_to_acc_h']} h, "
                  f"wh {row['energy_wh']}, deferred "
                  f"{row['policy_deferred']} {row['policy_skips']}")

    # ------------------------------------------------------------------
    # scenario 1 — the BENCH_degradation storm, deadline/quorum rounds
    # ------------------------------------------------------------------
    C, spc = (2, 3) if args.smoke else (5, 10)
    horizon_days = 0.5 if args.smoke else 1.0
    max_rounds = 4 if args.smoke else 12
    storm_start_s = 1_800.0
    storm_dur_s = (0.35 if args.smoke else 0.65) * horizon_days * 86_400
    K = C * spc
    # drop 1.0: a struck link NEVER delivers. degradation.py keeps 0.9 so
    # late deliveries exercise its straggler machinery; here the subject
    # is cohort selection, and partial delivery lets the built-in limp
    # along on carried stragglers — masking the selection difference
    storm_drop = 1.0
    fc_storm = storm_faults(C, storm_start_s, storm_dur_s, storm_drop)
    degrade = dict(round_deadline_s=1_800.0, quorum=1, max_retries=0,
                   late_policy="carry") if args.smoke else \
        dict(round_deadline_s=3_600.0, quorum=2, max_retries=2,
             late_policy="carry")
    cfg_base = dict(model="mlp", selection="scheduled",
                    clients_per_round=max(K // 5, 2), epochs=2,
                    batch_size=16, max_rounds=max_rounds, max_local_epochs=6,
                    lr=0.05)

    print(f"[policy] storm scenario on {C}x{spc}, {N_GS} GS, "
          f"{horizon_days:g} d horizon, storm over "
          f"{max(C - 1, 1)} plane(s) "
          f"({'smoke' if args.smoke else 'full'})")
    plan = build_contact_plan(C, spc, N_GS, horizon_s=horizon_days * 86_400,
                              dt_s=60.0)
    ds = make_federated_dataset("femnist", K, N_PER_CLIENT)

    gate_rows(plan, ds, [
        ("baseline", FLConfig(**cfg_base)),
        # the parity column: the explicit policy spelling of the built-in
        ("explicit_policy", FLConfig(policy="scheduled", **cfg_base)),
        ("storm_sched", FLConfig(faults=fc_storm, **degrade, **cfg_base)),
        ("storm_deadline", FLConfig(policy="deadline_aware", faults=fc_storm,
                                    **degrade, **cfg_base)),
        ("storm_oracle", FLConfig(policy="oracle", faults=fc_storm,
                                  **degrade, **cfg_base)),
    ])

    # gate 1 — explicit built-in policy bitwise-identical to policy=None
    base_recs, base_params = runs["baseline"]
    exp_recs, exp_params = runs["explicit_policy"]
    par_ok = ([_record_key(r) for r in base_recs]
              == [_record_key(r) for r in exp_recs]) \
        and _bitwise_equal(base_params, exp_params)
    if not par_ok:
        failures.append('policy="scheduled" NOT bitwise-identical to the '
                        "policy=None built-in")
    print(f"  built-in policy parity: {'OK' if par_ok else 'FAILED'}")

    # gate 2 — deadline_aware must actually have dodged storm footprints
    by = {r["workload"]: r for r in rows}
    if by["storm_deadline"]["policy_skips"].get("storm_exposed", 0) == 0:
        failures.append("storm_deadline: storm_exposed == 0 (the policy "
                        "never demoted a storm-struck candidate)")

    # gate 3 — TTA (full mode): scheduled through the storm pays >= 1.2x
    tta = {}
    if not args.smoke:
        d_tta = by["storm_deadline"]["time_to_acc_h"]
        s_tta = by["storm_sched"]["time_to_acc_h"]
        tta = {"target": TARGET_ACC, "deadline_aware_h": d_tta,
               "scheduled_h": s_tta,
               "oracle_h": by["storm_oracle"]["time_to_acc_h"]}
        if d_tta is None:
            failures.append(f"storm_deadline never reached {TARGET_ACC} "
                            "accuracy under the storm")
        elif s_tta is not None and s_tta < 1.2 * d_tta:
            failures.append(f"scheduled TTA {s_tta} h is not >= 1.2x the "
                            f"deadline_aware TTA {d_tta} h — the policy "
                            "did not separate from the built-in")
        print(f"  TTA({TARGET_ACC}): deadline_aware {d_tta} h vs "
              f"scheduled {s_tta} h (oracle {tta['oracle_h']} h)")

    # ------------------------------------------------------------------
    # scenario 2 — tight energy: soft SoC scoring vs the binary floor
    # ------------------------------------------------------------------
    Ce, spce = (2, 3) if args.smoke else (2, 5)
    Ke = Ce * spce
    e_days = 0.5 if args.smoke else 1.0
    e_rounds = 3 if args.smoke else 10
    # stratified pack state: half the fleet starts just above the binary
    # floor, half just below. The floor trains only the high half (a
    # label-skewed cohort under the non-IID split) until the low half
    # recharges past min_soc; energy_aware sees the whole sunlit fleet —
    # it defers only eclipsed low-SoC satellites to sunrise and
    # SoC-weights the rest — so its cohorts stay diverse from round one
    init_soc = tuple(0.48 if k % 2 == 0 else 0.42 for k in range(Ke))
    energy = EnergyConfig(battery_capacity_wh=1.5, initial_soc=init_soc,
                          min_soc=0.45)
    cfg_energy = dict(model="mlp", selection="scheduled",
                      clients_per_round=max(Ke // 2, 2), epochs=2,
                      batch_size=16, max_rounds=e_rounds,
                      max_local_epochs=6, lr=0.05, energy=energy)

    print(f"[policy] energy scenario on {Ce}x{spce}, {N_GS} GS, "
          f"{e_days:g} d horizon, {energy.battery_capacity_wh} Wh pack, "
          f"floor {energy.min_soc}")
    plan_e = build_contact_plan(Ce, spce, N_GS, horizon_s=e_days * 86_400,
                                dt_s=60.0)
    ds_e = make_federated_dataset("femnist", Ke, N_PER_CLIENT,
                                  alpha=0.3, seed=SEED)

    gate_rows(plan_e, ds_e, [
        ("energy_floor", FLConfig(**cfg_energy)),
        ("energy_aware", FLConfig(policy="energy_aware", **cfg_energy)),
    ])
    by = {r["workload"]: r for r in rows}

    # gate 4 — the soft policy must actually have deferred into sunlight
    if by["energy_aware"]["policy_skips"].get("eclipse_deferred", 0) == 0:
        failures.append("energy_aware: eclipse_deferred == 0 (the policy "
                        "never deferred an eclipsed candidate)")

    # gate 5 — Wh-to-accuracy (full mode): the soft policy reaches the
    # target on no more fleet energy than the binary floor
    wh = {}
    if not args.smoke:
        a_wh = by["energy_aware"]["wh_to_acc"]
        f_wh = by["energy_floor"]["wh_to_acc"]
        wh = {"target": TARGET_ACC, "energy_aware_wh": a_wh,
              "floor_wh": f_wh}
        if a_wh is None:
            failures.append(f"energy_aware never reached {TARGET_ACC} "
                            "accuracy on the tight pack")
        elif f_wh is not None and a_wh > f_wh:
            failures.append(f"energy_aware spent {a_wh} Wh to target vs "
                            f"the floor's {f_wh} Wh — the soft policy "
                            "did not beat the binary floor")
        print(f"  Wh-to-acc({TARGET_ACC}): energy_aware {a_wh} Wh vs "
              f"floor {f_wh} Wh")

    out = {
        "benchmark": "policy_sweep",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "storm_scale": {"clusters": C, "sats_per_cluster": spc,
                        "ground_stations": N_GS,
                        "horizon_days": horizon_days,
                        "max_rounds": max_rounds, "drop_prob": storm_drop,
                        "degrade": degrade},
        "energy_scale": {"clusters": Ce, "sats_per_cluster": spce,
                         "horizon_days": e_days, "max_rounds": e_rounds,
                         "battery_wh": energy.battery_capacity_wh,
                         "initial_soc": energy.initial_soc,
                         "min_soc": energy.min_soc},
        "target_accuracy": TARGET_ACC,
        "fault_seed": SEED,
        "sweep": rows,
        "parity": {"builtin_policy_bitwise": par_ok},
        "tta": tta,
        "wh_to_acc": wh,
        "failures": failures,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all policy parity + accounting gates passed")
    return rows


def run(fast: bool = True):
    """Entry point for benchmarks/run.py (CSV rows; exits on gate
    failure so --smoke CI catches a regressed policy)."""
    sys.argv = ["policy_sweep.py"] + (["--smoke"] if fast else []) \
        + ["--out", "BENCH_policy_smoke.json" if fast
           else "BENCH_policy.json"]
    return [{k: v for k, v in row.items() if k != "policy_skips"}
            for row in main()]


if __name__ == "__main__":
    main()
