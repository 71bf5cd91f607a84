"""Discrete-event core benchmark: batched world-event resolution
(``WorldTimeline.advance_through``, one searchsorted cursor advance per
event kind per decision point) vs the per-event Python loop (a heap pop
and an ``Event`` object per occurrence — the classical discrete-event
consumption the round engines would otherwise sit in) at small (5x5),
paper (10x10), and mega-constellation (40x40, dt=10s) scale, emitting
``BENCH_event_engine.json`` so the speedup is tracked across PRs.

Usage:
    PYTHONPATH=src python benchmarks/event_engine_perf.py
        [--scales small paper mega] [--out BENCH_event_engine.json] [--smoke]

The world timeline is the full FL event set — contact-window open/close,
eclipse entry/exit, fault outage/recovery, radiation resets — drawn from
the same CSR engines the round loop queries (``ContactPlan``,
``EnergySim``, ``FaultSim``). Both consumptions are parity-checked before
timing: identical per-kind counts and totals (and, in smoke, identical
per-event order between ``iter_events`` and ``events_between``).

The CLI exits nonzero if the mega-scale batched speedup drops below the
5x target (the event-processing-throughput claim of the event-engine PR).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core.contact_plan import build_contact_plan
from repro.orbit.constellation import WalkerStar, satellite_elements
from repro.orbit.eclipse import eclipse_series
from repro.sim.energy import EnergyConfig, EnergySim
from repro.sim.events import WorldTimeline
from repro.sim.faults import FaultConfig, FaultSim
from repro.sim.hardware import FLYCUBE
from repro.launch.compile_cache import use_compile_cache

SCALES = {
    # name: (clusters, sats/cluster, ground stations, horizon_s, dt_s)
    "small": (5, 5, 3, 86_400.0, 60.0),
    "paper": (10, 10, 5, 86_400.0, 30.0),
    "mega": (40, 40, 13, 86_400.0, 10.0),
}

ROUND_CADENCE_S = 1_800.0      # decision points: one FL round per 30 min
SPEEDUP_TARGET = 5.0


def _timeit(fn, repeat=3):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _build_world(nc, spc, gs, horizon, dt):
    """The full FL world at scale: contact plan + energy + faults, wired
    into a fresh WorldTimeline exactly as ``SpaceifiedFL.run`` does."""
    plan = build_contact_plan(nc, spc, gs, horizon_s=horizon, dt_s=dt)
    c = WalkerStar(nc, spc)
    raan, phase, _ = satellite_elements(c)
    times = np.arange(0.0, horizon, dt)
    packed = eclipse_series(c, raan, phase,
                            np.radians(c.inclination_deg), times,
                            packed=True)
    energy = EnergySim(times, packed, (FLYCUBE,) * c.n_sats,
                       EnergyConfig(battery_capacity_wh=10.0,
                                    eclipse_dt_s=dt))
    faults = FaultSim(FaultConfig(mean_up_s=7 * 3600.0,
                                  mean_down_s=1800.0,
                                  radiation_rate_per_day=2.0, seed=0),
                      c.n_sats, horizon)
    return plan, energy, faults


def _consume_per_event(tl: WorldTimeline, horizon: float) -> int:
    """The per-event Python loop: one heap pop, one Event object, one
    Python iteration per world occurrence."""
    n = 0
    for _ in tl.iter_events(horizon * 1.02):
        n += 1
    return n


def _consume_batched(tl: WorldTimeline, query_ts) -> int:
    """The round engine's consumption: one vectorized pass per decision
    point."""
    n = 0
    for t in query_ts:
        n += tl.advance_through(float(t))
    return n


def bench_scale(name: str, smoke: bool) -> dict:
    nc, spc, gs, horizon, dt = SCALES[name]
    if smoke:
        horizon = min(horizon, 21_600.0)
    t0 = time.perf_counter()
    plan, energy, faults = _build_world(nc, spc, gs, horizon, dt)
    t_world = time.perf_counter() - t0

    t0 = time.perf_counter()
    tl = WorldTimeline.for_fl(plan, energy, faults)
    t_build = time.perf_counter() - t0
    n_events = tl.remaining()

    q = max(int(horizon // ROUND_CADENCE_S), 2)
    query_ts = np.linspace(horizon / q, horizon * 1.02, q)  # + past-horizon

    if smoke:   # order parity: the two per-event views agree event-for-event
        a = WorldTimeline.for_fl(plan, energy, faults)
        b = WorldTimeline.for_fl(plan, energy, faults)
        sa = [(e.t, e.kind, e.key) for e in a.iter_events(horizon * 1.02)]
        sb = [(e.t, e.kind, e.key)
              for t in query_ts for e in b.events_between(float(t))]
        assert sa == sb, "per-event order parity failure"

    t_ev, n_ev = _timeit(
        lambda: _consume_per_event(
            WorldTimeline.for_fl(plan, energy, faults), horizon),
        repeat=1 if smoke else 3)
    t_ba, n_ba = _timeit(
        lambda: _consume_batched(
            WorldTimeline.for_fl(plan, energy, faults), query_ts),
        repeat=1 if smoke else 3)
    # a few fault-interval ends may land past the consumption cap; both
    # modes must agree exactly on everything inside it
    assert n_ev == n_ba <= n_events, \
        f"count parity failure: {n_ev} vs {n_ba} (of {n_events})"
    n_consumed = n_ev
    # per-kind parity (fresh timelines, one per mode)
    ta = WorldTimeline.for_fl(plan, energy, faults)
    _consume_per_event(ta, horizon)
    tb = WorldTimeline.for_fl(plan, energy, faults)
    _consume_batched(tb, query_ts)
    assert ta.stats.counts == tb.stats.counts, "per-kind parity failure"

    return {
        "clusters": nc, "sats_per_cluster": spc, "n_sats": nc * spc,
        "ground_stations": gs, "horizon_s": horizon, "dt_s": dt,
        "n_world_events": n_consumed,
        "decision_points": q,
        "per_kind": {k: int(v) for k, v in sorted(ta.stats.counts.items())},
        "world_build_s": round(t_world, 3),
        "timeline_build_s": round(t_build, 4),
        "per_event_s": round(t_ev, 5),
        "batched_s": round(t_ba, 5),
        "per_event_events_per_s": round(n_consumed / max(t_ev, 1e-9)),
        "batched_events_per_s": round(n_consumed / max(t_ba, 1e-9)),
        "speedup": round(t_ev / max(t_ba, 1e-9), 1),
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
    ap.add_argument("--out", default="BENCH_event_engine.json")
    args = ap.parse_args()
    scales = args.scales or (["small"] if args.smoke else list(SCALES))

    results = {}
    for name in scales:
        print(f"== {name}: {SCALES[name]}", flush=True)
        row = bench_scale(name, args.smoke)
        results[name] = row
        print(f"   {row['n_sats']} sats, {row['n_world_events']} world "
              f"events over {row['decision_points']} decision points | "
              f"per-event {row['per_event_s']:.3f}s "
              f"({row['per_event_events_per_s']:,} ev/s) -> batched "
              f"{row['batched_s']:.4f}s "
              f"({row['batched_events_per_s']:,} ev/s) | "
              f"{row['speedup']}x", flush=True)

    out = Path(args.out)
    out.write_text(json.dumps({"benchmark": "event_engine_perf",
                               "results": results}, indent=2) + "\n")
    print(f"wrote {out}")
    if not args.smoke and "mega" in results:
        if results["mega"]["speedup"] < SPEEDUP_TARGET:
            raise SystemExit("mega batched event-processing speedup below "
                             f"the {SPEEDUP_TARGET:g}x target")


if __name__ == "__main__":
    main()
