"""Graceful-degradation benchmark: deadline/quorum rounds vs wait-for-all
through a correlated storm.

A plane-wide storm (``StormConfig``: correlated regional events expanded
into the fault engine's outage/drop/corruption draws) pins most of the
constellation's transmission attempts to the floor for half a day. The
wait-for-all engine stalls: every synchronous round waits for the
storm-struck stragglers, so one round swallows the whole storm. The
deadline/quorum engine degrades instead: rounds close at
``round_deadline_s`` once ``quorum`` deliveries landed, the bounded
drop-retry walks (``max_retries`` + backoff) stop burning windows on
hopeless links, and late updates fold back later as staleness-discounted
deltas — so the surviving plane keeps the global model converging at the
normal cadence.

Gates (exit nonzero on violation):
  * frozen-ref parity: the defaults baseline (``storms=None``,
    ``round_deadline_s=inf``, ``max_retries=None``) is rerun through the
    retained pre-change engine (``repro.core.round_engine_ref``) and must
    be BITWISE identical — the degradation layer may not perturb the
    default path;
  * never-binding parity: a deadline too large to ever bind must
    reproduce the defaults baseline bitwise;
  * storm accounting: the storm columns must report ``storm_events > 0``
    (the injected storm actually intersected the run);
  * degradation accounting: the quorum column must report
    ``deadline_expired > 0`` (the close actually cut a round) and — full
    mode only, the smoke constellation is too sparse to attempt inside
    the storm — ``retries_exhausted > 0`` (the bounded walks gave up);
  * time-to-accuracy (full mode only — the smoke cohort is too small for
    a stable TTA): the quorum column must reach the target accuracy, and
    the wait-for-all column's TTA must be >= 2x worse (or never reach it
    at all).

Usage:
    PYTHONPATH=src python benchmarks/degradation.py \
        [--smoke] [--out BENCH_degradation.json]
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
from repro.sim.faults import FaultConfig, StormConfig, StormEvent
from repro.sim.hardware import SMALLSAT_SBAND
from repro.launch.compile_cache import use_compile_cache

N_GS = 3
N_PER_CLIENT = 32
TARGET_ACC = 0.5
SEED = 0


def _record_key(rec):
    return (rec.round, rec.t_start, rec.t_end, rec.duration_s, rec.idle_s,
            rec.comm_s, rec.train_s, rec.epochs, tuple(rec.participants),
            rec.accuracy, rec.skipped_faulted, rec.dropped_contacts,
            rec.retransmit_bytes, rec.deadline_expired,
            rec.stragglers_carried, rec.retries_exhausted, rec.storm_events)


def _bitwise_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _tta_h(recs, target: float):
    for r in recs:
        if r.accuracy >= target:
            return round((r.t_end - recs[0].t_start) / 3600, 3)
    return None


def storm_faults(n_clusters: int, t_start_s: float, duration_s: float,
                 drop_prob: float):
    """A correlated storm over all but the last plane: transmission
    attempts from struck planes drop with high probability while it
    rages (no outages — the satellites are up, their links are dead), so
    the fate of a round is decided purely by the round-close policy.
    ``drop_prob`` below 1 lets some struck walks deliver *late* (they
    become deadline stragglers) while others exhaust their bounded
    budget — exercising both degradation paths."""
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
        "deadline_expired": int(sum(r.deadline_expired for r in recs)),
        "stragglers_carried": int(sum(r.stragglers_carried for r in recs)),
        "retries_exhausted": int(sum(r.retries_exhausted for r in recs)),
        "storm_events": int(sum(r.storm_events for r in recs)),
        "skipped_faulted": int(sum(r.skipped_faulted for r in recs)),
        "dropped_contacts": int(sum(r.dropped_contacts for r in recs)),
        "retransmit_mb": round(sum(r.retransmit_bytes for r in recs)
                               / 1e6, 3),
        "wall_s": round(wall, 2),
        "traces": train_cache_sizes()["local_sgd_clients"],
    }
    return algo, recs, row


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_degradation.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smaller constellation, fewer rounds")
    args = ap.parse_args()

    C, spc = (2, 3) if args.smoke else (5, 10)
    horizon_days = 0.5 if args.smoke else 1.0
    max_rounds = 4 if args.smoke else 12
    storm_start_s = 1_800.0                      # 0.5 h in: hits round 2+
    storm_dur_s = (0.35 if args.smoke else 0.65) * horizon_days * 86_400
    K = C * spc
    cfg_base = dict(model="mlp", clients_per_round=max(K // 5, 2), epochs=2,
                    batch_size=16, max_rounds=max_rounds, max_local_epochs=6,
                    lr=0.05)
    storm_drop = 1.0 if args.smoke else 0.9
    fc_storm = storm_faults(C, storm_start_s, storm_dur_s, storm_drop)
    # quorum must sit strictly below the cohort width (== cohort is the
    # wait-for-all identity); the smoke cohort of 2 also needs a
    # zero-retry budget so a single storm drop visibly exhausts a walk.
    # Full mode gives walks a real budget so some struck walks deliver
    # late (deadline stragglers) while others exhaust.
    degrade = dict(round_deadline_s=1_800.0, quorum=1, max_retries=0,
                   late_policy="carry") if args.smoke else \
        dict(round_deadline_s=3_600.0, quorum=2, max_retries=2,
             late_policy="carry")

    print(f"[degradation] fedavg on {C}x{spc}, {N_GS} GS, {horizon_days:g} d "
          f"horizon, storm over {C - 1 if C > 1 else 1} plane(s) "
          f"[{storm_start_s / 3600:g} h, +{storm_dur_s / 3600:g} h] "
          f"({'smoke' if args.smoke else 'full'})")
    plan = build_contact_plan(C, spc, N_GS, horizon_s=horizon_days * 86_400,
                              dt_s=60.0)
    ds = make_federated_dataset("femnist", K, N_PER_CLIENT)

    cols = [
        ("baseline", FLConfig(**cfg_base)),
        # never-binding deadline: the parity column for the new config
        ("deadline_unbound", FLConfig(round_deadline_s=1e12, quorum=1,
                                      **cfg_base)),
        ("storm_waitall", FLConfig(faults=fc_storm, **cfg_base)),
        ("storm_quorum", FLConfig(faults=fc_storm, **degrade, **cfg_base)),
    ]
    rows, failures, runs = [], [], {}
    for name, cfg in cols:
        algo, recs, row = run_point(name, plan, ds, cfg)
        rows.append(row)
        runs[name] = (recs, algo.global_params)
        if row["rounds"] and row["traces"] != 1:
            failures.append(f"{name}: trainer traced {row['traces']}x")
        print(f"  {name:>16}: {row['rounds']} rounds, best_acc "
              f"{row['best_acc']}, tta {row['time_to_acc_h']} h, "
              f"mean_round {row['mean_round_h']} h, expired "
              f"{row['deadline_expired']}, carried "
              f"{row['stragglers_carried']}, rex "
              f"{row['retries_exhausted']}, storms {row['storm_events']}")

    # gate 1 — defaults baseline bitwise vs the frozen pre-change engine
    base_recs, base_params = runs["baseline"]
    clear_train_caches()
    ref = RER.FedAvgSatRef(plan, SMALLSAT_SBAND, ds, FLConfig(**cfg_base))
    ref_recs = ref.run()
    ref_ok = ([_record_key(r) for r in base_recs]
              == [_record_key(r) for r in ref_recs]) \
        and _bitwise_equal(base_params, ref.global_params)
    if not ref_ok:
        failures.append("defaults baseline NOT bitwise-identical to "
                        "round_engine_ref (degradation layer perturbed "
                        "the default path)")
    print(f"  parity vs round_engine_ref: {'OK' if ref_ok else 'FAILED'}")

    # gate 2 — a deadline that can never bind must be the baseline bitwise
    ub_recs, ub_params = runs["deadline_unbound"]
    ub_ok = ([_record_key(r) for r in base_recs]
             == [_record_key(r) for r in ub_recs]) \
        and _bitwise_equal(base_params, ub_params)
    if not ub_ok:
        failures.append("never-binding deadline NOT bitwise-identical to "
                        "wait-for-all defaults")
    print(f"  never-binding-deadline parity: {'OK' if ub_ok else 'FAILED'}")

    # gate 3 — the storm must actually have intersected both storm runs
    by = {r["workload"]: r for r in rows}
    for col in ("storm_waitall", "storm_quorum"):
        if by[col]["storm_events"] == 0:
            failures.append(f"{col}: storm_events == 0 (the storm never "
                            "intersected a round)")

    # gate 4 — the degradation machinery must actually have fired (the
    # retry-exhaustion leg is full-mode only: the smoke constellation is
    # too sparse to reliably attempt a transmission *inside* the storm)
    q = by["storm_quorum"]
    if q["deadline_expired"] == 0:
        failures.append("storm_quorum: deadline_expired == 0 (the close "
                        "never cut a round)")
    if not args.smoke and q["retries_exhausted"] == 0:
        failures.append("storm_quorum: retries_exhausted == 0 (the bounded "
                        "walks never gave up)")

    # gate 5 — time-to-accuracy (full mode): quorum rounds keep converging
    # through the storm; wait-for-all pays >= 2x or never gets there
    tta = {}
    if not args.smoke:
        q_tta, w_tta = q["time_to_acc_h"], by["storm_waitall"]["time_to_acc_h"]
        tta = {"target": TARGET_ACC, "quorum_h": q_tta, "waitall_h": w_tta}
        if q_tta is None:
            failures.append(f"storm_quorum never reached {TARGET_ACC} "
                            "accuracy under the storm")
        elif w_tta is not None and w_tta < 2.0 * q_tta:
            failures.append(f"wait-for-all TTA {w_tta} h is not >= 2x the "
                            f"quorum TTA {q_tta} h — the storm did not "
                            "separate the policies")
        print(f"  TTA({TARGET_ACC}): quorum {q_tta} h vs wait-for-all "
              f"{w_tta} h")

    out = {
        "benchmark": "degradation",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "scale": {"clusters": C, "sats_per_cluster": spc,
                  "ground_stations": N_GS, "horizon_days": horizon_days,
                  "n_per_client": N_PER_CLIENT, "max_rounds": max_rounds},
        "storm": {"t_start_h": storm_start_s / 3600,
                  "duration_h": storm_dur_s / 3600,
                  "planes_struck": max(C - 1, 1), "drop_prob": storm_drop},
        "degrade": degrade,
        "target_accuracy": TARGET_ACC,
        "fault_seed": SEED,
        "sweep": rows,
        "parity": {"vs_round_engine_ref": ref_ok,
                   "never_binding_deadline": ub_ok},
        "tta": tta,
        "failures": failures,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all degradation parity + accounting gates passed")


if __name__ == "__main__":
    main()
