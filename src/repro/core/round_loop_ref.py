"""The pre-event-engine main loops, retained as the golden-parity baseline
(the ``_ref.py`` convention — like ``round_engine_ref`` for the padded
dispatch and ``energy_ref`` for the battery integrator).

Until the discrete-event core (``repro.sim.events``), every algorithm
advanced time with one of two Python loops: the synchronous engines with a
round-by-round ``while`` over ``run_round``, and FedBuffSat with an ad-hoc
``heapq`` of ``(return_time, sat)`` tuples. ``SpaceifiedFL.run`` /
``FedBuffSat.run`` now drive the same per-round math from a deterministic
:class:`~repro.sim.events.EventQueue`; the loops below are the *exact*
pre-port control flow, and the differential scenario-matrix suite
(``tests/test_event_parity.py``) asserts the event-driven engines produce
bitwise-identical ``RoundRecord`` streams against them across
(engine x fleet mix x energy x faults x quant_bits). Do not "optimize"
this module — its value is being frozen.

Usage: build a *fresh* algorithm instance and run it through
:func:`run_loop` instead of calling ``algo.run()``. The functions mutate
the instance exactly like the old methods did (records, key stream,
energy/fault state), so an instance must not be run twice.
"""
from __future__ import annotations

import heapq
from typing import Dict, Optional

import numpy as np


def run_loop(algo, t0: float = 0.0, t_end: Optional[float] = None,
             max_rounds: Optional[int] = None):
    """Dispatch to the retained loop matching ``algo``'s class."""
    from repro.core.spaceify import FedBuffSat
    if isinstance(algo, FedBuffSat):
        return run_fedbuff_loop(algo, t0, t_end, max_rounds)
    return run_sync_loop(algo, t0, t_end, max_rounds)


def run_sync_loop(algo, t0: float = 0.0, t_end: Optional[float] = None,
                  max_rounds: Optional[int] = None):
    """The pre-event-engine ``SpaceifiedFL.run``: a round-by-round while
    loop whose clock is the previous round's ``t_end``."""
    t_end = t_end if t_end is not None else algo.plan.horizon_s
    max_rounds = max_rounds or algo.cfg.max_rounds
    t = t0
    r = 0
    while r < max_rounds and t < t_end:
        rec = algo.run_round(r, t)
        if rec is None:
            break
        algo.records.append(rec)
        t = rec.t_end
        r += 1
    return algo.records


def run_fedbuff_loop(algo, t0: float = 0.0, t_end: Optional[float] = None,
                     max_rounds: Optional[int] = None):
    """The pre-event-engine ``FedBuffSat.run``: the ad-hoc ``heapq`` of
    ``(return_time, sat)`` tuples (ties break on the satellite index by
    tuple comparison — the ordering the EventQueue port must preserve),
    with the PR 5-7 energy deferral, fault re-scheduling, and payload-
    fault semantics exactly as shipped. A return queues the same pending
    entry as ``FedBuffSat.run`` and the buffer trains and folds in the
    engine's own ``_flush_buffer``, so the parity gate stays about the
    clock."""
    cfg, plan = algo.cfg, algo.plan
    t_end = t_end if t_end is not None else plan.horizon_s
    max_rounds = max_rounds or cfg.max_rounds
    K = plan.constellation.n_sats

    ep_s = algo.fleet.epoch_time_s            # (K,) per-satellite
    heap = []
    client_params: Dict[int, object] = {}
    pickup_round: Dict[int, int] = {}
    epochs_of: Dict[int, int] = {}
    idle_of: Dict[int, float] = {}
    deferred_up: Dict[int, float] = {}
    pickup_t: Dict[int, float] = {}
    meta_of: Dict[int, tuple] = {}
    tq = np.full(K, t0)
    if algo.energy is not None:
        algo.energy.advance_to(t0)
        drained = np.nonzero(~algo.energy.eligible())[0]
        if len(drained):
            rts = algo.energy.recover_times(drained)
            tq[drained] = np.where(np.isfinite(rts),
                                   np.maximum(rts, t0), np.inf)
    if algo.faults is None:
        avail, _, _, valid = plan.next_contacts(tq)
        recv_end_k = avail + algo._t_up_k
        ret_avail, _, _, ret_valid = plan.next_contacts(
            np.where(valid, recv_end_k + ep_s, np.inf))
        for k in range(K):
            if not (valid[k] and ret_valid[k]):
                continue
            recv_end, ret0 = float(recv_end_k[k]), float(ret_avail[k])
            ep = int(np.clip((ret0 - recv_end) // ep_s[k], 1,
                             cfg.max_local_epochs))
            heapq.heappush(heap, (ret0 + float(algo._t_down_k[k]), k))
            client_params[k] = algo._tx_global()
            pickup_round[k] = 0
            epochs_of[k] = ep
            idle_of[k] = max(ret0 - (recv_end + ep * float(ep_s[k])), 0.0)
            if algo.energy is not None:
                deferred_up[k] = float(algo._t_up_k[k])
    else:
        tq = algo.faults.next_up(np.arange(K), tq)
        for k in range(K):
            w = algo._next_available_contact(k, float(tq[k]))
            if w is None:
                continue
            recv_end = float(w[0]) + float(algo._t_up_k[k])
            nxt = algo._next_available_contact(k, recv_end + float(ep_s[k]))
            if nxt is None:
                continue
            ep = int(np.clip((nxt[0] - recv_end) // ep_s[k], 1,
                             cfg.max_local_epochs))
            t_done, d, rb, lost = algo._walk_drops(k, nxt)
            if lost:
                continue
            heapq.heappush(heap, (t_done, k))
            client_params[k] = algo._tx_global()
            pickup_round[k] = 0
            epochs_of[k] = ep
            idle_of[k] = max(nxt[0] - (recv_end + ep * float(ep_s[k])), 0.0)
            pickup_t[k] = float(w[0])
            meta_of[k] = (d, rb)
            if algo.energy is not None:
                deferred_up[k] = float(algo._t_up_k[k])

    buf, r = [], 0
    t_round_start = t0
    idle_acc, comm_acc, train_acc, n_ev = 0.0, 0.0, 0.0, 0
    energy_acc, skip_acc = 0.0, 0
    fault_acc, drop_acc, rebill_acc = 0, 0, 0.0
    comm_by: Dict[int, float] = {}
    while heap and r < max_rounds:
        t_ret, k = heapq.heappop(heap)
        if t_ret > t_end:
            break
        t_up, t_down = float(algo._t_up_k[k]), float(algo._t_down_k[k])
        train_s = epochs_of[k] * float(ep_s[k])
        wiped = (algo.faults is not None and algo.faults.cfg.has_resets
                 and algo.faults.reset_in(k, pickup_t.get(k, t0), t_ret))
        n_drops = 0
        if not wiped:
            stale = r - pickup_round[k]
            wgt = (1.0 + stale) ** (-cfg.staleness_exponent)
            buf.append((k, client_params[k], epochs_of[k], wgt, t_ret))
            comm_acc += t_up + t_down
            comm_by[k] = comm_by.get(k, 0.0) + t_up + t_down
            train_acc += train_s
            idle_acc += idle_of.get(k, 0.0)
            n_ev += 1
            if algo.faults is not None:
                n_drops, rb = meta_of.get(k, (0, 0.0))
                drop_acc += n_drops
                rebill_acc += rb
                comm_acc += n_drops * t_down
                comm_by[k] = comm_by.get(k, 0.0) + n_drops * t_down
        else:
            fault_acc += 1
            deferred_up.pop(k, None)
        recv_end = t_ret + t_up
        requeue, stood_down = True, False
        if algo.energy is not None:
            algo.energy.advance_to(t_ret)
            if not wiped:
                energy_acc += algo.energy.bill_activity(
                    np.array([k]), np.array([train_s]),
                    np.array([t_down * (1 + n_drops)
                              + deferred_up.pop(k, 0.0)]))
            if not algo.energy.eligible()[k]:
                skip_acc += 1
                stood_down = True
                w2 = algo._post_recovery_contact(k, recv_end)
                if w2 is None:
                    requeue = False
                else:
                    recv_end = w2[0] + t_up
        nxt = algo._next_available_contact(k, recv_end + float(ep_s[k])) \
            if requeue else None
        ev_t, d2, rb2 = None, 0, 0.0
        if nxt is not None:
            ev_t = float(nxt[0]) + t_down
            if algo.faults is not None:
                t_done2, d2, rb2, lost = algo._walk_drops(k, nxt)
                if lost:
                    nxt = None
                else:
                    ev_t = t_done2
        if nxt is not None:
            if algo.energy is not None:
                if stood_down:
                    deferred_up[k] = t_up
                else:
                    energy_acc += algo.energy.bill_activity(
                        np.array([k]), np.array([0.0]), np.array([t_up]))
            ep = int(np.clip((nxt[0] - recv_end) // ep_s[k], 1,
                             cfg.max_local_epochs))
            heapq.heappush(heap, (ev_t, k))
            client_params[k] = algo._tx_global()
            pickup_round[k] = r
            epochs_of[k] = ep
            idle_of[k] = max(nxt[0] - (recv_end + ep * float(ep_s[k])), 0.0)
            if algo.faults is not None:
                pickup_t[k] = recv_end - t_up
                meta_of[k] = (d2, rb2)
        elif algo.energy is not None or algo.faults is not None:
            for dct in (client_params, pickup_round, epochs_of,
                        idle_of, deferred_up, pickup_t, meta_of):
                dct.pop(k, None)

        if len(buf) >= cfg.buffer_size:
            algo._flush_buffer(buf)
            buf = []
            acc = algo.evaluate() if r % cfg.eval_every == 0 else \
                (algo.records[-1].accuracy if algo.records else 0.0)
            dur = t_ret - t_round_start
            from repro.core.spaceify import RoundRecord
            algo.records.append(RoundRecord(
                r, t_round_start, t_ret, dur,
                idle_acc / max(n_ev, 1),
                comm_acc / max(n_ev, 1), train_acc / max(n_ev, 1),
                acc, [],
                epochs=float(np.mean(list(epochs_of.values())))
                if epochs_of else 0.0,
                energy_wh=energy_acc, skipped_low_power=skip_acc,
                comm_s_by_sat=comm_by, skipped_faulted=fault_acc,
                dropped_contacts=drop_acc, retransmit_bytes=rebill_acc,
                corrupted_updates=algo._last_flush_corrupted,
                clipped_updates=algo._last_flush_clipped))
            t_round_start = t_ret
            idle_acc = comm_acc = train_acc = 0.0
            energy_acc, skip_acc = 0.0, 0
            fault_acc, drop_acc, rebill_acc = 0, 0, 0.0
            comm_by = {}
            n_ev = 0
            r += 1
    return algo.records
