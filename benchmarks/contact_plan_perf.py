"""Contact-plan engine benchmark: plan construction + a simulated
scheduling workload at small (5x5), paper (10x10), and mega-constellation
(40x40, dt=10s) scale, comparing the vectorized structure-of-arrays engine
against the retained reference scalar scans and emitting
``BENCH_contact_plan.json`` so the speedup is tracked across PRs.

Usage:
    PYTHONPATH=src python benchmarks/contact_plan_perf.py [--scales small paper mega]
        [--out BENCH_contact_plan.json] [--queries 40]

The scheduling workload replays the scheduler's hot path: at each of Q
epochs spread over the horizon, score the whole constellation with a
projected-return pass (initial contact -> uplink -> train -> return
contact) and select the top clients — exactly what
``SpaceifiedFL.select_clients`` does every round. Vectorized and reference
selections are asserted identical (parity), then timed.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core import contact_plan_ref as ref
from repro.core.contact_plan import ContactPlan
from repro.orbit.constellation import WalkerStar, satellite_elements
from repro.orbit.groundstations import gs_ecef
from repro.orbit.visibility import (elevation_mask_series,
                                    windows_from_bool_tensor)
from repro.launch.compile_cache import use_compile_cache

SCALES = {
    # name: (clusters, sats/cluster, ground stations, horizon_s, dt_s)
    "small": (5, 5, 3, 86_400.0, 30.0),
    "paper": (10, 10, 5, 86_400.0, 30.0),
    "mega": (40, 40, 5, 21_600.0, 10.0),
}

T_UP = 2.0          # synthetic link/compute budget for the workload
T_DOWN = 2.0
T_TRAIN = 600.0
CLIENTS_PER_ROUND = 10


def _timeit(fn, repeat=3):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def select_vectorized(plan: ContactPlan, t: float):
    """Batched projected-return scoring (SpaceifiedFL.select_clients with
    the Intra-SL augmentation when the constellation supports it)."""
    avail, _, _, v1 = plan.next_contacts(t)
    train_end = avail + T_UP + T_TRAIN
    ret, _, _, _, v2 = plan.next_cluster_contacts(train_end)
    valid = v1 & v2
    score = ret + T_DOWN
    ks = np.nonzero(valid)[0]
    order = np.lexsort((ks, score[ks]))
    return [int(k) for k in ks[order][:CLIENTS_PER_ROUND]]


def select_reference(plan: ContactPlan, t: float):
    """The original per-satellite linear-scan projection (peer scans for
    the Intra-SL return relay)."""
    cands = []
    for k in range(plan.constellation.n_sats):
        w = ref.next_contact_ref(plan.sat_windows, k, t)
        if w is None:
            continue
        train_end = w[0] + T_UP + T_TRAIN
        r = ref.next_cluster_contact_ref(plan, k, train_end)
        if r is None:
            continue
        cands.append((r[0] + T_DOWN, k))
    cands.sort()
    return [k for _, k in cands[:CLIENTS_PER_ROUND]]


def bench_scale(name: str, n_queries: int) -> dict:
    nc, spc, n_gs, horizon, dt = SCALES[name]
    c = WalkerStar(nc, spc)
    raan, phase, cluster = satellite_elements(c)
    times = np.arange(0.0, horizon, dt)
    gs = gs_ecef(n_gs)
    incl = np.radians(c.inclination_deg)

    t0 = time.perf_counter()
    vis = elevation_mask_series(c, raan, phase, incl, times, gs)
    t_mask = time.perf_counter() - t0

    # window extraction: one-diff-pass tensor sweep vs (K, G) Python loop
    t_extract_vec, flat = _timeit(
        lambda: windows_from_bool_tensor(vis, times), repeat=3)
    t_extract_ref, wins_ref = _timeit(
        lambda: ref.access_windows_ref(vis, times), repeat=1)
    sat, gsi, s, e = flat
    plan = ContactPlan.from_window_arrays(c, horizon, sat, gsi, s, e,
                                          cluster_of=cluster)
    assert plan.sat_windows == wins_ref, "window extraction parity failure"
    n_windows = sum(len(w) for w in plan.sat_windows)

    # scheduling workload: Q selection epochs across the horizon
    query_ts = np.linspace(0.0, horizon * 0.8, n_queries)

    def run_vec():
        return [select_vectorized(plan, float(t)) for t in query_ts]

    def run_ref():
        return [select_reference(plan, float(t)) for t in query_ts]

    t_sched_vec, sel_vec = _timeit(run_vec, repeat=3)
    t_sched_ref, sel_ref = _timeit(run_ref, repeat=1)
    assert sel_vec == sel_ref, "scheduling parity failure"

    row = {
        "clusters": nc, "sats_per_cluster": spc, "n_sats": c.n_sats,
        "ground_stations": n_gs, "horizon_s": horizon, "dt_s": dt,
        "n_windows": n_windows, "n_queries": n_queries,
        "elevation_mask_s": round(t_mask, 4),
        "extract_vectorized_s": round(t_extract_vec, 5),
        "extract_reference_s": round(t_extract_ref, 5),
        "extract_speedup": round(t_extract_ref / max(t_extract_vec, 1e-9), 1),
        "sched_vectorized_s": round(t_sched_vec, 5),
        "sched_reference_s": round(t_sched_ref, 5),
        "sched_speedup": round(t_sched_ref / max(t_sched_vec, 1e-9), 1),
        "parity": True,
    }
    return row


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scales", nargs="+", default=list(SCALES),
                    choices=list(SCALES))
    ap.add_argument("--queries", type=int, default=40,
                    help="selection epochs in the scheduling workload")
    ap.add_argument("--out", default="BENCH_contact_plan.json")
    args = ap.parse_args()

    results = {}
    for name in args.scales:
        print(f"== {name}: {SCALES[name]}", flush=True)
        row = bench_scale(name, args.queries)
        results[name] = row
        print(f"   {row['n_sats']} sats, {row['n_windows']} windows | "
              f"extract {row['extract_reference_s']:.3f}s -> "
              f"{row['extract_vectorized_s']:.3f}s "
              f"({row['extract_speedup']}x) | "
              f"sched {row['sched_reference_s']:.3f}s -> "
              f"{row['sched_vectorized_s']:.3f}s "
              f"({row['sched_speedup']}x)", flush=True)

    out = Path(args.out)
    out.write_text(json.dumps({"benchmark": "contact_plan_perf",
                               "results": results}, indent=2) + "\n")
    print(f"wrote {out}")
    if "mega" in results and results["mega"]["sched_speedup"] < 10:
        raise SystemExit("mega scheduling speedup below the 10x target")


if __name__ == "__main__":
    main()
