"""AutoFLSat (paper §3.3, Algorithm 2): fully autonomous hierarchical FL.

Two-tier aggregation with NO central parameter server:
  * tier 1 — each orbital cluster runs synchronous FL over its always-on
    Intra-Satellite Links (every satellite trains e epochs, cluster model is
    the data-weighted average);
  * tier 2 — cluster models are exchanged over Inter-Satellite Links whenever
    plane pairs have line-of-sight; the InterSLScheduler chains the
    C(C-1)/2 pairwise passes needed for all-to-all sharing and derives the
    per-round epoch budget e from the first/last comms record.

Ground access is needed only to seed w_0 (and optionally to offload).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregation import segment_mean, segment_weighted_mean
from repro.core.client import local_sgd_clients
from repro.core.contact_plan import ContactPlan
from repro.core.quantize import quantize_roundtrip_stacked
from repro.core.spaceify import (_WALK_ATTEMPT_CAP, FLConfig, RoundRecord,
                                 SpaceifiedFL)


@dataclasses.dataclass
class InterSLSchedule:
    t_start: float
    t_complete: float          # all pairwise exchanges done
    epochs: int                # training budget derived from the schedule
    passes: List[Tuple[int, int, float]]   # (ci, cj, t_exchange)
    # fault accounting (zeros when FLConfig.faults is off)
    dropped_contacts: int = 0          # ISL hop attempts lost to drops
    retransmit_bytes: float = 0.0      # re-billed bytes of retried hops
    # graceful-degradation accounting (zeros at wait-for-all defaults)
    retries_exhausted: int = 0         # pair hops abandoned: retry budget out
    pairs_skipped: int = 0             # pair exchanges skipped (deadline or
                                       # exhaustion) instead of failing the
                                       # round


def _fleet_mean(a) -> float:
    """Mean of a per-satellite array, exact for a uniform fleet: summing
    K equal doubles and dividing by K is not an IEEE identity, and the
    uniform fleet must reproduce the scalar primary-profile record fields
    bitwise (the round-engine parity suite compares them with ==)."""
    a = np.asarray(a, np.float64)
    first = a.flat[0]
    return float(first) if np.all(a == first) else float(np.mean(a))


class AutoFLSat(SpaceifiedFL):
    name = "autoflsat"

    def __init__(self, plan: ContactPlan, hw, dataset, cfg: FLConfig,
                 epochs_mode: str = "fixed"):
        self.epochs_mode = epochs_mode       # "fixed" | "auto"
        super().__init__(plan, hw, dataset, cfg)

    def _init(self, plan: ContactPlan, hw, dataset, cfg: FLConfig):
        super()._init(plan, hw, dataset, cfg)
        C = plan.constellation.n_clusters
        self.n_clusters = C
        # per-cluster models start from the seeded w_0
        self.cluster_params = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (C,) + p.shape), self.global_params)
        self.cluster_acc: List[float] = []

    # ------------------------------------------------------------------
    def inter_sl_scheduler(self, t: float) -> Optional[InterSLSchedule]:
        """Algorithm 2's InterSLScheduler: chain the C(C-1)/2 pair passes.

        Heterogeneous fleets: each pairwise exchange is bottlenecked by
        the slowest ISL radio among the two clusters' members (the
        cluster model must cross that pair's weakest link), so pair
        passes get per-pair durations. A uniform fleet reduces to the
        single scalar duration of the primary-profile engine."""
        C = self.n_clusters
        if C == 1:
            # no pair passes to chain: the round end is entirely the
            # tier-1 train+exchange completion, which run_round computes
            # over the *participating* satellites (a schedule-side max
            # over all members would let a battery-masked slow satellite
            # gate a round it sits out; for an all-eligible fleet
            # run_round's t_train_done >= this anyway, so dropping the
            # train time here is behavior-neutral).
            return InterSLSchedule(t, t, self.cfg.epochs, [])
        spc = self.plan.constellation.sats_per_cluster
        rate_c = self.fleet.isl_rate_bps.reshape(C, spc).min(1)
        tx = {(ci, cj):
              self.tx_bytes * 8.0 / min(rate_c[ci], rate_c[cj]) * 2.0
              for ci in range(C) for cj in range(ci + 1, C)}  # bidirectional
        drops, rebill, rex, skipped = 0, 0.0, 0, 0
        if self.faults is None and not self._deadline_on:
            chained = self.plan.chain_pair_transfers(t, tx)
            if chained is None:
                return None
            t_cur, passes = chained
        else:
            t_deadline = t + self.cfg.round_deadline_s \
                if self._deadline_on else np.inf
            chained = self._chain_pair_transfers_faulted(t, tx, t_deadline)
            if chained is None:
                return None
            t_cur, passes, drops, rebill, rex, skipped = chained
        if self.epochs_mode == "auto":
            # epochs from first & last comms record (Algorithm 2); the
            # budget must fit the slowest ML unit so tier 1 stays in sync
            e = max(1, int((t_cur - t)
                           // float(np.max(self.fleet.epoch_time_s))))
            e = min(e, self.cfg.max_local_epochs)
        else:
            e = self.cfg.epochs
        return InterSLSchedule(t, t_cur, e, passes, drops, rebill,
                               rex, skipped)

    def _chain_pair_transfers_faulted(self, t: float, tx: dict,
                                      t_deadline: float = np.inf):
        """Fault-aware pair chain: each ISL hop's transmission attempt
        may drop independently (``faults.pair_dropped``, keyed by the
        attempt time, so every retry is a fresh seeded draw). A dropped
        hop spends its airtime, re-bills the pair's bytes both ways, and
        stalls the cluster sync until the next pair *window* — the drop
        is the fate of the whole exchange attempt, so the retry
        re-acquires at the next pass rather than microseconds later in
        the same one. Returns (t_complete, passes, dropped_hops,
        retransmit_bytes, retries_exhausted, pairs_skipped) or None when
        a hop runs out of windows in wait-for-all mode.

        Graceful degradation (dead at the defaults, so the wait-for-all
        fault path is bitwise the PR 7 chain): with ``cfg.max_retries``
        set, each pair hop gets the same bounded budget + window-level
        exponential backoff as the downlink walk, and an exhausted hop
        *skips* that pair's exchange (counted, the chain continues)
        instead of burning retries forever. With a finite round deadline
        a pair whose exchange cannot complete by ``t_deadline`` — or
        whose windows run out mid-walk — is likewise skipped rather than
        failing the whole round: the storm-struck pair degrades to a
        missing exchange, the rest of the hierarchy keeps syncing. Also
        serves the faults-None + deadline-on combination (drop draws
        skipped, deadline skipping active)."""
        C = self.n_clusters
        t_cur = t
        passes: List[Tuple[int, int, float]] = []
        drops, rebill, rex, skipped = 0, 0.0, 0, 0
        bounded = self.cfg.max_retries is not None
        budget = self.cfg.max_retries if bounded else _WALK_ATTEMPT_CAP
        deadline_on = bool(np.isfinite(t_deadline))
        for ci in range(C):
            for cj in range(ci + 1, C):
                dur = tx[(ci, cj)]
                attempts = 0
                while True:
                    done = self.plan.transmit_over_pair(ci, cj, t_cur, dur)
                    if done is None:
                        if deadline_on:
                            skipped += 1    # degrade: drop this exchange
                            break
                        return None
                    if deadline_on and done > t_deadline:
                        skipped += 1        # cannot land before the close
                        break
                    if self.faults is None or \
                            not self.faults.pair_dropped(ci, cj, t_cur):
                        passes.append((ci, cj, t_cur))
                        t_cur = done
                        break
                    drops += 1
                    attempts += 1
                    rebill += 2.0 * self.tx_bytes   # both directions lost
                    if attempts > budget:
                        rex += 1
                        skipped += 1
                        t_cur = done    # the failed attempt spent airtime
                        break
                    # airtime was spent through ``done``; skip the rest of
                    # the pass the failed attempt ended in and retry at
                    # the next pair window (strictly later, so the walk
                    # always terminates and every retry keys a new draw)
                    w = self.plan.next_pair_window(ci, cj, done)
                    if bounded:     # window-level exponential backoff
                        for _ in range((1 << min(attempts - 1, 16)) - 1):
                            if w is None:
                                break
                            w = self.plan.next_pair_window(ci, cj,
                                                           float(w[1]))
                    if w is None:
                        if deadline_on:
                            skipped += 1
                            t_cur = done
                            break
                        return None
                    t_cur = float(w[1]) if w[0] <= done else float(w[0])
        return t_cur, passes, drops, rebill, rex, skipped

    # ------------------------------------------------------------------
    def run_round(self, r, t):
        cfg, plan = self.cfg, self.plan
        with obs.span("fl.select"):
            sched = self.inter_sl_scheduler(t)
        if sched is None:
            return None
        e = sched.epochs
        C = self.n_clusters
        spc = plan.constellation.sats_per_cluster

        # battery gating: sats below the SoC floor sit the round out (zero
        # weight in the cluster mean; the K-wide dispatch shape is fixed
        # either way, so nothing retraces)
        energy_ok = None
        if self.energy is not None:
            self.energy.advance_to(t)
            energy_ok = self.energy.eligible()
        # fault gating composes by boolean AND into the same mask (order
        # immaterial): members inside an outage at round start, or reset
        # by radiation before their train+exchange completes, carry zero
        # weight in the cluster mean. ``ok is None`` == everyone in.
        K = C * spc
        # per-member tier-1 epoch budgets (selection-policy layer): a
        # policy with ``member_budgets`` maps its score inputs — fleet
        # epoch times, SoC, the round deadline — to a (K,) budget, so a
        # slow or drained member trains fewer epochs instead of
        # stretching the synchronous barrier. The trainer takes epochs
        # as a per-client dynamic argument, so the budget vector never
        # retraces the K-wide dispatch. None (every built-in policy)
        # keeps the scalar schedule budget — the bitwise pre-policy path.
        ep_k = None
        if self.policy.member_budgets:
            ep_k = self.policy.epoch_budgets(
                self._policy_inputs(None, t, e), e)
        if ep_k is not None:
            ep_k = np.asarray(ep_k, np.int32)
            train_time_k = self.fleet.train_time(ep_k)       # (K,)
        else:
            train_time_k = self.fleet.train_time(sched.epochs)   # (K,)
        intra_comm_k = self._t_isl_k * 2.0                   # bidirectional
        done_k = t + train_time_k + intra_comm_k
        ok = energy_ok
        n_flt = 0
        if self.faults is not None:
            fault_ok = self.faults.available(t)
            if self.faults.cfg.has_resets:
                fault_ok = fault_ok & (self.faults.resets_between(
                    np.arange(K), t, done_k) == 0)
            n_flt = int(np.sum(~fault_ok)) if ok is None \
                else int(np.sum(ok & ~fault_ok))
            # an all-True fault mask is not folded in: with energy off the
            # round must keep ok=None and take the exact segment_mean
            # tier-2 path, so a never-firing FaultConfig stays
            # bitwise-identical to faults=None (weighted mean with all-one
            # weights is not an IEEE identity for the plain mean)
            if not bool(fault_ok.all()):
                ok = fault_ok if ok is None else ok & fault_ok

        # tier 1: synchronous intra-cluster FL (all satellites participate)
        # as ONE (C*spc)-wide vmapped dispatch + a segment-wise cluster
        # aggregation — no per-cluster Python loop, so the trainer compiles
        # once for the whole constellation.
        with obs.span("fl.train"):
            ks = jax.random.split(self.key, K + 1)
            self.key = ks[0]
            keys = ks[1:]                    # sat (c, s) gets row c*spc + s
            bcast = self.cluster_params
            if cfg.quant_bits:               # every transmitted model is
                bcast = quantize_roundtrip_stacked(bcast, cfg.quant_bits)
            stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(
                    p[:, None], (C, spc) + p.shape[1:]).reshape(
                        (K,) + p.shape[1:]), bcast)
            trained = local_sgd_clients(
                cfg.model, stacked, self.ds.x, self.ds.y,
                keys, ep_k if ep_k is not None else e,
                cfg.batch_size, cfg.lr)
            if cfg.quant_bits:               # member -> cluster-head return
                trained = quantize_roundtrip_stacked(trained, cfg.quant_bits)

        # silent payload faults: member k's trained model crosses the
        # intra-cluster ISL to its cluster head at done_k[k]; the delivery
        # may be SEU-corrupted or poisoned. Members already masked out
        # (ok False) deliver nothing, so they draw nothing.
        n_corr, n_clip = 0, 0
        if self.faults is not None and self.faults.cfg.has_payload_faults:
            for kk in range(K):
                if ok is not None and not ok[kk]:
                    continue
                ref_c = jax.tree.map(lambda b: b[kk // spc], bcast)
                trained, bad = self._corrupt_row(
                    trained, kk, kk, float(done_k[kk]), ref_c)
                n_corr += int(bad)

        # deadline / quorum close on the tier-1 barrier: with a finite
        # round deadline, members whose train + intra-cluster exchange
        # lands after the close are stragglers — carried as stale deltas
        # (late_policy "carry") or discarded — instead of stretching the
        # synchronous barrier through a storm. Dead when the deadline is
        # inf, so the default barrier stays bitwise-identical.
        n_exp, n_strag = 0, 0
        t_close = None
        if self._deadline_on:
            elig = np.ones(K, bool) if ok is None else np.asarray(ok, bool)
            t_close, on_time, expired = self._close_round(t, done_k, elig)
            if expired:
                n_exp = 1
                late_members = np.nonzero(elig & ~on_time)[0]
                n_strag = int(len(late_members))
                if cfg.late_policy == "carry":
                    for kk in late_members:
                        ref_c = jax.tree.map(
                            lambda b, _kk=int(kk): b[_kk // spc], bcast)
                        self._carry_straggler(trained, int(kk), ref_c,
                                              float(done_k[kk]), r, int(kk))
                ok = on_time if ok is None else (ok & on_time)
            if sched.pairs_skipped:
                n_exp = 1   # tier-2 exchanges were cut short by the close

        # tier 2: all-to-all exchange -> constellation-wide model (the
        # exchanged cluster models cross ISLs quantized when quant_bits>0)
        with obs.span("fl.aggregate"):
            if ok is None:
                stacked_clusters = segment_mean(trained, C)
                self.global_params, n_clip = self._aggregate(
                    stacked_clusters, np.full(C, float(spc)))
                self.cluster_params = jax.tree.map(
                    lambda g: jnp.broadcast_to(g, (C,) + g.shape),
                    self.global_params)
            else:
                w = ok.astype(np.float64)
                seg_w = w.reshape(C, spc).sum(1)   # eligible sats per cluster
                if seg_w.sum() > 0:
                    stacked_clusters = segment_weighted_mean(
                        trained, jnp.asarray(w, jnp.float32), C)
                    # clusters with no eligible members carry zero tier-2
                    # weight
                    self.global_params, n_clip = self._aggregate(
                        stacked_clusters, seg_w)
                    self.cluster_params = jax.tree.map(
                        lambda g: jnp.broadcast_to(g, (C,) + g.shape),
                        self.global_params)
                # else: the whole fleet is below the floor — models
                # unchanged, the round still advances time (the exchange
                # slots were spent)

        # timing: training overlaps the exchange chain; the round ends when
        # both the last pairwise pass and local training are done. Each
        # member trains and exchanges on its own hardware — the slowest
        # *participating* satellite gates the synchronous tier-1 phase
        # (a battery-masked member trains nothing, so it cannot stretch
        # the round it sits out; the tier-2 pair schedule stays the
        # conservative whole-cluster bottleneck, since the orbital
        # exchange slots are fixed before SoC is known).
        if t_close is not None:
            # deadline mode: the barrier ends at the close, not at the
            # slowest straggler (equal to the participant max when the
            # deadline never bound)
            t_train_done = float(t_close)
        elif ok is not None and ok.any():
            t_train_done = float(np.max(done_k[ok]))
        else:
            t_train_done = float(np.max(done_k))
        t_round_end = max(sched.t_complete, t_train_done)
        idle = max(t_round_end - t_train_done, 0.0)
        # fold stale straggler deltas whose delivery landed by this
        # round's end (FedBuff-style staleness discount), then refresh
        # the per-cluster broadcast copies of the patched global model
        if self._carried:
            with obs.span("fl.aggregate"):
                if self._fold_carried(t_round_end, r):
                    self.cluster_params = jax.tree.map(
                        lambda g: jnp.broadcast_to(g, (C,) + g.shape),
                        self.global_params)
        K = plan.constellation.n_sats
        participants = list(range(K))
        wh, skipped = 0.0, 0
        if ok is not None:
            participants = [k for k in range(K) if ok[k]]
        if energy_ok is not None:
            skipped = int(np.sum(~energy_ok))
            self.energy.advance_to(t_round_end)
            ksel = np.asarray(participants, np.int64)
            wh = self.energy.bill_activity(
                ksel, train_time_k[ksel], intra_comm_k[ksel]) \
                if len(ksel) else 0.0
        acc = self.evaluate() if r % cfg.eval_every == 0 else \
            (self.records[-1].accuracy if self.records else 0.0)
        # per-member comm: own intra-cluster exchanges + this member's
        # share of the tier-2 pass chain. Record means cover the
        # *participants* (like comm_s_by_sat and the energy bill); with
        # energy off everyone participates and the exact-mean shortcut
        # keeps the uniform fleet bitwise-identical to the scalar engine.
        comm_k = intra_comm_k * 2 \
            + len(sched.passes) * self._t_isl_k * 2.0 / max(C, 1)
        psel = np.asarray(participants, np.int64)
        comm_rec = _fleet_mean(comm_k[psel]) if len(psel) else 0.0
        train_rec = _fleet_mean(train_time_k[psel]) if len(psel) else 0.0
        # cluster-model divergence (paper §5.2): per-cluster accuracies
        return RoundRecord(r, t, t_round_end, t_round_end - t, idle,
                           comm_rec, train_rec, acc, participants,
                           epochs=float(np.mean(ep_k)) if ep_k is not None
                           else float(e), energy_wh=wh,
                           skipped_low_power=skipped,
                           comm_s_by_sat={k: float(comm_k[k])
                                          for k in participants},
                           skipped_faulted=n_flt,
                           dropped_contacts=sched.dropped_contacts,
                           retransmit_bytes=sched.retransmit_bytes,
                           corrupted_updates=n_corr,
                           clipped_updates=n_clip,
                           deadline_expired=n_exp,
                           stragglers_carried=n_strag,
                           retries_exhausted=sched.retries_exhausted,
                           storm_events=self._storms_in(t, t_round_end))
