"""Fixed-shape round engine benchmark: rounds/sec + trace counts for the
padded compile-once engine vs the retained pre-change engine
(``repro.core.round_engine_ref``) at 2x5 and 5x10 constellation scale,
with in-run golden parity asserted (identical participant sets, round
timings and accuracy trajectories; bitwise-identical global params after
5 rounds at quant_bits=0).

The headline workload is a FLySTacK design-space sweep (paper §4): the
same FedAvgSat config swept over ground-station counts {1, 2, 3}. Each
sweep point decays through a different set of cohort sizes near the
horizon, so the pre-change engine re-traces the local-SGD scan for every
distinct width of every sweep point (8 traces at 5x10), while the padded
engine compiles exactly once for the whole sweep — the recompile overhead
that makes large sweeps impractical is what this benchmark meters. A
conv-bound cnn run is reported for context (rounds dominated by conv
FLOPs: engines tie), and a quant_bits=8 run drives the live QuAFL path
through the quant_agg kernel route.

Usage:
    PYTHONPATH=src python benchmarks/round_engine_perf.py \
        [--smoke] [--scales 2x5 5x10] [--out BENCH_round_engine.json]

Exit is nonzero if any parity check fails, if the padded engine traces
``local_sgd_clients`` more than once per algorithm workload, or (full
mode) if the 5x10 sweep speedup regresses below 2.5x (the structural
ratio is ~3.0-3.4x; the guard sits a notch below so CPU-contention noise
cannot flake a healthy run — the checked-in reference run shows >= 3x).
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
from repro.core.spaceify import FedAvgSat, FedProxSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.sim.hardware import SMALLSAT_SBAND
import repro.models.small as small_models
from repro.launch.compile_cache import use_compile_cache

SCALES = {
    # name: (clusters, sats/cluster, horizon_days, sweep gs counts)
    "2x5": (2, 5, 1.0, (1, 2, 3)),
    "5x10": (5, 10, 1.0, (1, 2, 3)),
}
N_PER_CLIENT = 16

NEW_ALGOS = {"fedavg": FedAvgSat, "fedprox": FedProxSat}
REF_ALGOS = {"fedavg": RER.FedAvgSatRef, "fedprox": RER.FedProxSatRef}


def _cfg(scale, model, max_rounds, **kw):
    C, spc, _, _ = SCALES[scale]
    base = dict(model=model, clients_per_round=max(2, C * spc // 2),
                epochs=2, batch_size=16, max_rounds=max_rounds,
                max_local_epochs=8, lr=0.05)
    base.update(kw)
    return FLConfig(**base)


def _record_key(rec):
    return (rec.round, rec.t_start, rec.t_end, rec.duration_s, rec.idle_s,
            rec.comm_s, rec.train_s, rec.epochs, tuple(rec.participants))


def _bitwise_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _fresh_caches():
    """Cold-start every engine run: each workload pays its own traces and
    compiles (jax.clear_caches also drops the eager-vmap executables the
    seed engine hides in the global compilation caches)."""
    jax.clear_caches()
    clear_train_caches()
    RER.clear_ref_trace_count()
    small_models._ACC_FNS.clear()


def run_workload(name, scale, algorithm, plan_list, ds, cfg,
                 check_speedup=None, repeats=2):
    """Time ref vs padded engine over a (possibly multi-plan sweep)
    workload on identical configs; assert parity point by point. Each
    engine runs ``repeats`` times from cold caches and the best wall is
    kept (PR-1 benchmark convention, damping CPU-contention noise)."""
    failures = []
    runs = {}
    for eng, algos in (("ref", REF_ALGOS), ("new", NEW_ALGOS)):
        wall = float("inf")
        for _ in range(repeats):
            _fresh_caches()
            algos_out, recs_out = [], []
            t0 = time.perf_counter()
            for plan in plan_list:
                algo = algos[algorithm](plan, SMALLSAT_SBAND, ds, cfg)
                recs_out.append(algo.run())
                algos_out.append(algo)
            wall = min(wall, time.perf_counter() - t0)
            traces = (RER.ref_trace_count() if eng == "ref"
                      else train_cache_sizes()["local_sgd_clients"])
        runs[eng] = dict(algos=algos_out, recs=recs_out, wall=wall,
                         traces=traces)

    ref, new = runs["ref"], runs["new"]
    n_rounds = sum(len(r) for r in new["recs"])
    for i, (rr, nr) in enumerate(zip(ref["recs"], new["recs"])):
        if [_record_key(x) for x in rr] != [_record_key(x) for x in nr]:
            failures.append(f"{name}[{i}]: timings/selections diverged")
        if not cfg.quant_bits:
            if [x.accuracy for x in rr] != [x.accuracy for x in nr]:
                failures.append(f"{name}[{i}]: accuracy diverged")
            if not _bitwise_equal(ref["algos"][i].global_params,
                                  new["algos"][i].global_params):
                failures.append(f"{name}[{i}]: params not bitwise identical")
    if new["traces"] > 1:
        failures.append(f"{name}: padded engine traced local_sgd_clients "
                        f"{new['traces']}x (must be <= 1 per algorithm)")

    speedup = ref["wall"] / new["wall"] if n_rounds else float("nan")
    if check_speedup is not None and speedup < check_speedup:
        failures.append(f"{name}: speedup {speedup:.2f}x < "
                        f"{check_speedup:.1f}x target")

    widths = sorted({len(r.participants)
                     for recs in new["recs"] for r in recs})
    row = {
        "workload": name, "scale": scale, "algorithm": algorithm,
        "model": cfg.model, "quant_bits": cfg.quant_bits,
        "clients_per_round": cfg.clients_per_round,
        "sweep_points": len(plan_list),
        "rounds": n_rounds, "cohort_widths": widths,
        "ref_wall_s": round(ref["wall"], 3),
        "new_wall_s": round(new["wall"], 3),
        "ref_rounds_per_s": round(n_rounds / ref["wall"], 4),
        "new_rounds_per_s": round(n_rounds / new["wall"], 4),
        "speedup": round(speedup, 3),
        "ref_traces": ref["traces"], "new_traces": new["traces"],
        "parity_rounds_checked": n_rounds,
        "bitwise_params": bool(not cfg.quant_bits and not any(
            "bitwise" in f for f in failures)),
    }
    print(f"  {name}: {n_rounds} rounds over {len(plan_list)} sweep "
          f"point(s), widths={widths} | ref {ref['wall']:.1f}s "
          f"({ref['traces']} traces) vs new {new['wall']:.1f}s "
          f"({new['traces']} traces) => {speedup:.2f}x")
    return row, failures


def five_round_bitwise_check(scale, plan, ds):
    """The acceptance check verbatim: 5 rounds, quant_bits=0, bitwise."""
    cfg = _cfg(scale, "mlp", max_rounds=5)
    _fresh_caches()
    ref = RER.FedAvgSatRef(plan, SMALLSAT_SBAND, ds, cfg)
    ref.run()
    _fresh_caches()
    new = FedAvgSat(plan, SMALLSAT_SBAND, ds, cfg)
    new.run()
    ok = _bitwise_equal(ref.global_params, new.global_params) \
        and len(ref.records) == len(new.records) == 5
    print(f"  {scale}: 5-round bitwise parity: {'OK' if ok else 'FAILED'}")
    return ok


def quant_kernel_in_sim_check(scale, plan, ds):
    """quant_bits>0 must route the sim's aggregation through quant_agg:
    the Pallas kernel (interpret) and the jnp fallback must agree."""
    finals = {}
    for mode in ("jnp", "pallas_interpret"):
        cfg = _cfg(scale, "mlp", max_rounds=3, quant_bits=8,
                   quant_kernel=mode)
        _fresh_caches()
        algo = FedAvgSat(plan, SMALLSAT_SBAND, ds, cfg)
        algo.run()
        finals[mode] = algo.global_params
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree_util.tree_leaves(finals["jnp"]),
                               jax.tree_util.tree_leaves(
                                   finals["pallas_interpret"])))
    ok = diff < 1e-4      # two accumulation orders over a whole cohort
    print(f"  quant_agg in-sim parity (pallas interpret vs jnp): "
          f"maxdiff={diff:.2e} {'OK' if ok else 'FAILED'}")
    return ok


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", nargs="+", default=None,
                    choices=list(SCALES))
    ap.add_argument("--out", default="BENCH_round_engine.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: 2x5 only, few rounds, no speed gates")
    args = ap.parse_args()
    scales = args.scales or (["2x5"] if args.smoke else ["2x5", "5x10"])
    max_rounds = 6 if args.smoke else 500

    plans, datasets = {}, {}
    for s in scales:
        C, spc, days, gs_sweep = SCALES[s]
        for gs in gs_sweep:
            plans[(s, gs)] = build_contact_plan(
                C, spc, gs, horizon_s=days * 86400, dt_s=60.0)
        datasets[s] = make_federated_dataset("femnist", C * spc,
                                             N_PER_CLIENT)

    rows, failures = [], []
    for s in scales:
        gs_sweep = SCALES[s][3]
        sweep_plans = [plans[(s, gs)] for gs in gs_sweep]
        base_plan = plans[(s, gs_sweep[-1])]
        print(f"[{s}]")
        # headline: ground-station design sweep — gate 3x at 5x10 full mode
        # regression guard: the structural ratio of this workload is
        # ~3.0-3.4x (see the checked-in BENCH_round_engine.json); gate a
        # notch below so CPU-contention noise can't flake a healthy run
        gate = 2.5 if (s == "5x10" and not args.smoke) else None
        row, f = run_workload(
            f"fedavg_{s}_gs_sweep", s, "fedavg",
            sweep_plans if not args.smoke else sweep_plans[-1:],
            datasets[s], _cfg(s, "mlp", max_rounds), check_speedup=gate,
            repeats=1 if args.smoke else 2)
        rows.append(row)
        failures += f
        row, f = run_workload(f"fedprox_{s}_mlp", s, "fedprox",
                              [base_plan], datasets[s],
                              _cfg(s, "mlp", max_rounds),
                              repeats=1 if args.smoke else 2)
        rows.append(row)
        failures += f
        if s == "5x10" and not args.smoke:
            # conv-bound context run: rounds are dominated by conv FLOPs,
            # engines should tie (no speed gate, parity still enforced)
            row, f = run_workload(f"fedavg_{s}_cnn", s, "fedavg",
                                  [base_plan], datasets[s],
                                  _cfg(s, "cnn", max_rounds), repeats=1)
            rows.append(row)
            failures += f
        if not five_round_bitwise_check(s, base_plan, datasets[s]):
            failures.append(f"{s}: 5-round bitwise parity failed")

    # live QuAFL path: quantized rounds/sec + in-sim kernel parity
    print("[quant]")
    s0 = scales[0]
    base_plan0 = plans[(s0, SCALES[s0][3][-1])]
    qrow, f = run_workload(
        f"fedavg_{s0}_mlp_q8", s0, "fedavg", [base_plan0], datasets[s0],
        _cfg(s0, "mlp", max_rounds, quant_bits=8),
        repeats=1 if args.smoke else 2)
    rows.append(qrow)
    # ref engine bills quantized bytes but trains/aggregates f32, while the
    # new engine really quantizes — timings must still agree (same wire
    # size), params won't: keep only timing/trace failures for this row
    failures += [x for x in f if "timings" in x or "traced" in x]
    if not quant_kernel_in_sim_check(s0, base_plan0, datasets[s0]):
        failures.append("quant_agg in-sim parity failed")

    out = {
        "benchmark": "round_engine_perf",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "n_per_client": N_PER_CLIENT,
        "scales": {s: dict(zip(("clusters", "sats_per_cluster",
                                "horizon_days", "gs_sweep"),
                               SCALES[s])) for s in scales},
        "workloads": rows,
        "failures": failures,
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all parity + trace-count + speed gates passed")


if __name__ == "__main__":
    main()
