"""Plain reference of the simulated world: Walker-star orbits, ground
contact windows, inter-plane line-of-sight windows and the two schedules
the benchmark's simulator cells run (FedAvg with first-contact selection,
AutoFLSat's chained inter-plane exchange).

Everything here is float64 numpy written from the configuration file's
stated geometry and hardware; it imports nothing of the program under
test. ``dtype`` lowers the precision of the geometry for the control.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import List, Optional, Tuple

import numpy as np

R_EARTH = 6_371_000.0          # m, spherical Earth
MU_EARTH = 3.986004418e14      # m^3 / s^2
OMEGA_EARTH = 7.2921159e-5     # rad / s, sidereal rotation


@dataclasses.dataclass(frozen=True)
class World:
    planes: int
    per_plane: int
    altitude_m: float
    inclination_deg: float
    phase_offset_frac: float
    stations: Tuple[Tuple[float, float], ...]     # (lat_deg, lon_deg)
    min_elev_deg: float
    horizon_s: float
    dt_s: float
    isl_max_range_m: float
    isl_clearance_m: float
    epoch_time_s: float
    uplink_bps: float
    downlink_bps: float
    isl_bps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "World":
        c, g, hw = cfg["constellation"], cfg["ground"], cfg["hardware"]
        return cls(planes=c["planes"], per_plane=c["sats_per_plane"],
                   altitude_m=c["altitude_km"] * 1e3,
                   inclination_deg=c["inclination_deg"],
                   phase_offset_frac=c["phase_offset_frac"],
                   stations=tuple(tuple(s) for s in g["stations_lat_lon_deg"]),
                   min_elev_deg=g["min_elevation_deg"],
                   horizon_s=cfg["horizon_days"] * 86_400.0,
                   dt_s=cfg["grid_s"],
                   isl_max_range_m=c["isl_max_range_km"] * 1e3,
                   isl_clearance_m=c["isl_earth_clearance_km"] * 1e3,
                   epoch_time_s=hw["epoch_time_s"],
                   uplink_bps=hw["uplink_bps"],
                   downlink_bps=hw["downlink_bps"],
                   isl_bps=hw["isl_bps"])

    @property
    def n_sats(self) -> int:
        return self.planes * self.per_plane

    def times(self) -> np.ndarray:
        return np.arange(0.0, self.horizon_s, self.dt_s)


def eci(world: World, times: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Satellite positions (T, K, 3) in an inertial frame: circular orbits,
    planes with RAAN spread over 180 degrees, satellites evenly phased in
    a plane and planes offset by ``phase_offset_frac`` of a slot."""
    p = np.repeat(np.arange(world.planes), world.per_plane)
    s = np.tile(np.arange(world.per_plane), world.planes)
    raan = np.pi * p / world.planes
    phase = 2 * np.pi * s / world.per_plane \
        + 2 * np.pi * world.phase_offset_frac * p / world.n_sats
    a = R_EARTH + world.altitude_m
    n = np.sqrt(MU_EARTH / a ** 3)
    inc = np.radians(world.inclination_deg)
    u = (phase[None, :] + n * times[:, None]).astype(dtype)
    raan = raan.astype(dtype)
    x = a * (np.cos(raan) * np.cos(u) - np.sin(raan) * np.sin(u) * np.cos(inc))
    y = a * (np.sin(raan) * np.cos(u) + np.cos(raan) * np.sin(u) * np.cos(inc))
    z = a * np.sin(u) * np.sin(inc)
    return np.stack([x, y, z], axis=-1).astype(dtype)


def ground_visibility(world: World, dtype=np.float64) -> np.ndarray:
    """(T, K, G) bool: station g sees satellite k at or above the
    elevation mask at grid time t (Earth-fixed frame)."""
    t = world.times()
    pos = eci(world, t, dtype)
    th = (-OMEGA_EARTH * t[:, None]).astype(dtype)
    xe = pos[..., 0] * np.cos(th) - pos[..., 1] * np.sin(th)
    ye = pos[..., 0] * np.sin(th) + pos[..., 1] * np.cos(th)
    sat = np.stack([xe, ye, pos[..., 2]], axis=-1).astype(dtype)
    lat = np.radians([s[0] for s in world.stations])
    lon = np.radians([s[1] for s in world.stations])
    up = np.stack([np.cos(lat) * np.cos(lon), np.cos(lat) * np.sin(lon),
                   np.sin(lat)], axis=-1)
    gs = (R_EARTH * up).astype(dtype)
    rel = sat[:, :, None, :] - gs[None, None]
    rng = np.sqrt(np.sum(rel * rel, axis=-1, dtype=dtype))
    sin_el = np.sum(rel * up.astype(dtype), axis=-1, dtype=dtype) \
        / np.maximum(rng, 1.0)
    return sin_el >= np.sin(np.radians(world.min_elev_deg))


def runs(vis: np.ndarray, times: np.ndarray, dt: float
         ) -> List[Tuple[float, float]]:
    """Contiguous True runs of a (T,) series as (first sample, last sample
    + dt) windows."""
    out, start = [], None
    for i, v in enumerate(vis):
        if v and start is None:
            start = i
        elif not v and start is not None:
            out.append((float(times[start]), float(times[i - 1]) + dt))
            start = None
    if start is not None:
        out.append((float(times[start]), float(times[-1]) + dt))
    return out


def ground_windows(world: World, dtype=np.float64):
    """Per satellite, its (start, end, station) windows sorted by start,
    then end, then station."""
    vis = ground_visibility(world, dtype)
    t = world.times()
    out = []
    for k in range(world.n_sats):
        wins = [(s, e, g) for g in range(vis.shape[2])
                for s, e in runs(vis[:, k, g], t, world.dt_s)]
        out.append(sorted(wins))
    return out


def isl_windows(world: World, a: int, b: int, dtype=np.float64):
    """Line-of-sight windows between satellites a and b: within range and
    the segment between them clear of the Earth by the stated margin."""
    t = world.times()
    pos = eci(world, t, dtype)
    pa, pb = pos[:, a], pos[:, b]
    d = pb - pa
    rng2 = np.sum(d * d, axis=-1)
    lam = np.clip(-np.sum(pa * d, axis=-1) / np.maximum(rng2, 1.0), 0.0, 1.0)
    closest = pa + lam[:, None] * d
    clear = np.sqrt(np.sum(closest * closest, axis=-1)) \
        > R_EARTH + world.isl_clearance_m
    vis = (np.sqrt(rng2) <= world.isl_max_range_m) & clear
    return runs(vis, t, world.dt_s)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------


def next_window(wins, t: float) -> Optional[Tuple[float, float, int]]:
    """The first window (in start order) that ends after ``t``; a pass in
    progress counts and is available from ``t``."""
    for s, e, g in wins:
        if e > t:
            return max(s, t), e, g
    return None


def fedavg_schedule(world: World, windows, model_bytes: float, width: int,
                    epochs: int, max_rounds: int):
    """FedAvg with first-contact selection. Each round starts when the
    previous one ends; the ``width`` satellites whose next contact comes
    first (ties by index) receive the model, train ``epochs`` epochs and
    return it at their next contact after training. The round ends at the
    last return. Returns [(t_start, t_end, participants)]."""
    t_up = model_bytes * 8.0 / world.uplink_bps
    t_down = model_bytes * 8.0 / world.downlink_bps
    train = epochs * world.epoch_time_s
    t, rounds = 0.0, []
    while len(rounds) < max_rounds and t < world.horizon_s:
        cands = []
        for k, wins in enumerate(windows):
            first = next_window(wins, t)
            if first is None:
                continue
            back = next_window(wins, first[0] + t_up + train)
            if back is None:
                continue
            cands.append((first[0], k, back[0] + t_down))
        if not cands:
            break
        chosen = sorted(cands)[:width]
        t_end = max(c[2] for c in chosen)
        rounds.append((t, t_end, [c[1] for c in chosen]))
        t = t_end
    return rounds


def fedbuff_schedule(world: World, windows, model_bytes: float, buffer: int,
                     max_epochs: int, max_rounds: int):
    """FedBuff: every satellite picks up the global model at its first
    contact, trains as many whole epochs as fit before its next contact
    (at least 1, at most ``max_epochs``) and returns its update there,
    picking up the current global model at once. Returns are handled in
    (time, satellite) order; every ``buffer`` returns the server folds
    the buffer into the global model, which ends a round. Returns
    ([(t_start, t_end, [], mean epoch budget)], [(satellite, epochs,
    round of pickup)] per return)."""
    t_up = model_bytes * 8.0 / world.uplink_bps
    t_down = model_bytes * 8.0 / world.downlink_bps
    ep_s = world.epoch_time_s
    budget = lambda back, recv: int(min(max((back - recv) // ep_s, 1),
                                        max_epochs))
    heap, epochs, picked = [], {}, {}
    for k, wins in enumerate(windows):
        first = next_window(wins, 0.0)
        back = None if first is None else \
            next_window(wins, first[0] + t_up + ep_s)
        if back is None:
            continue
        epochs[k], picked[k] = budget(back[0], first[0] + t_up), 0
        heapq.heappush(heap, (back[0] + t_down, k))
    rounds, events, t_round, held = [], [], 0.0, 0
    while heap and len(rounds) < max_rounds:
        t, k = heapq.heappop(heap)
        if t > world.horizon_s:
            break
        events.append((k, epochs[k], picked[k]))
        recv = t + t_up
        back = next_window(windows[k], recv + ep_s)
        if back is not None:
            epochs[k], picked[k] = budget(back[0], recv), len(rounds)
            heapq.heappush(heap, (back[0] + t_down, k))
        held += 1
        if held >= buffer:
            rounds.append((t_round, t, [],
                           float(np.mean(list(epochs.values())))))
            t_round, held = t, 0
    return rounds, events


def transmit(wins, t: float, airtime: float) -> Optional[float]:
    """When ``airtime`` seconds of link time, starting no earlier than
    ``t``, are complete over the windows ``wins`` (a transfer resumes in
    the next window when one ends)."""
    left = airtime
    for s, e in wins:
        if e <= t:
            continue
        start = max(s, t)
        if e - start >= left:
            return start + left
        left -= e - start
    return None


def autoflsat_schedule(world: World, pair_windows, model_bytes: float,
                       max_epochs: int, max_rounds: int):
    """AutoFLSat without a ground station: every satellite trains each
    round while the planes exchange their models pairwise over
    inter-plane links, one pair after another in (i, j) order, each
    exchange both ways. The epoch budget is what fits in the exchange
    chain, capped at ``max_epochs``; the round ends when both the chain
    and the training with its two intra-plane hops are done. Returns
    [(t_start, t_end, epochs)]."""
    pair_s = model_bytes * 8.0 / world.isl_bps * 2.0
    hop = model_bytes * 8.0 / world.isl_bps * 2.0
    t, rounds = 0.0, []
    while len(rounds) < max_rounds and t < world.horizon_s:
        t_cur = t
        for i in range(world.planes):
            for j in range(i + 1, world.planes):
                t_cur = transmit(pair_windows[(i, j)], t_cur, pair_s)
                if t_cur is None:
                    return rounds
        e = min(max(1, int((t_cur - t) // world.epoch_time_s)), max_epochs)
        t_end = max(t_cur, t + e * world.epoch_time_s + hop)
        rounds.append((t, t_end, e))
        t = t_end
    return rounds


def all_pair_windows(world: World, dtype=np.float64):
    """ISL windows between the first satellites of every pair of planes."""
    return {(i, j): isl_windows(world, i * world.per_plane,
                                j * world.per_plane, dtype)
            for i in range(world.planes) for j in range(i + 1, world.planes)}
