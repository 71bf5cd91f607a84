"""The space-ification framework (paper §3.1) + augmentations (§3.2).

Space-ification of an FL algorithm = three modular revisions:
  1. client selection: first C idle clients to contact a ground station
     (communication windows are too scarce to sample randomly);
  2. round completion: wait until every selected client re-contacts a GS to
     return weights (no always-on links);
  3. evaluation clients re-selected with the same contact protocol.

Augmentations (applicable to any space-ified algorithm):
  * ``scheduled`` — FLSchedule (Alg. 5): deterministic orbits => prioritize
    clients with the smallest initial-contact + revisit total;
  * ``intra_sl`` — FLIntraSL (Alg. 6): weights may return via any same-plane
    peer that reaches a ground station first.

Algorithms: FedAvgSat (Alg. 1), FedProxSat (Alg. 3, partial updates +
proximal term, V2 adds a min-epoch floor), FedBuffSat (Alg. 4, async
buffered aggregation with staleness discounting).

Performance — the fixed-shape round engine
------------------------------------------
Training cohorts are padded to the static ``cfg.clients_per_round`` width:
``_train_cohort`` fills unused slots with client 0's data and a dummy PRNG
key, and gives them ZERO aggregation weight, so
``repro.core.client.local_sgd_clients`` sees one shape per configuration
and compiles exactly once per (model, batch_size, mu_on, cohort width) no
matter how per-round eligibility fluctuates. The padded-cohort invariant:

  * selection order is computed BEFORE padding, on the same batched
    contact-plan projections as always — padding only widens the training
    dispatch, so participant sets and round timings are identical to the
    unpadded engine (asserted by ``benchmarks/round_engine_perf.py``);
  * masked slots carry weight 0 in ``weighted_average`` /
    ``quantized_weighted_average``, whose order-pinned accumulation forces
    zero-weight terms to exact +0 (even for non-finite rows) before a
    strictly sequential fold — appending pad slots is an IEEE identity, so
    ``quant_bits=0`` global params stay bitwise equal to the unpadded path;
  * per-slot PRNG keys are split ``len(sel)+1`` at a time exactly like the
    unpadded engine (pad slots reuse the first client key), so the key
    stream — and therefore training — is reproducible across both paths.

When ``cfg.quant_bits > 0`` the transmitted models are now ACTUALLY
quantized (QuAFL wire format), not just billed: the broadcast global is
round-tripped through ``quantize_roundtrip`` and the returned cohort is
aggregated with ``quantized_weighted_average``, which routes the
dequantize+accumulate through the ``quant_agg`` Pallas kernel (compiled on
TPU, jnp fallback elsewhere; ``cfg.quant_kernel`` overrides).

Heterogeneous fleets (per-satellite hardware)
---------------------------------------------
The ``hw`` argument may be one ``HardwareProfile`` (uniform fleet), a
``FleetProfile``, or a length-K profile sequence. Timing is always read
from the vectorized fleet arrays — ``(K,)`` uplink/downlink/ISL times and
epoch durations — so a mixed FLyCube / S-band constellation times every
satellite with its own radio and ML unit. A uniform fleet evaluates the
exact same IEEE operations as the scalar primary-profile engine, so it
stays bitwise-identical (``tests/test_fleet.py``,
``benchmarks/fleet_mix_perf.py`` gate this). With ``FLConfig.energy``
set, the battery simulation defaults to the same fleet, so power and
timing always bill the same hardware (the shared-fleet invariant;
``EnergyConfig.fleet`` can still override power-only what-ifs).

Energy gating (``FLConfig.energy``)
-----------------------------------
With an ``EnergyConfig`` set, every algorithm consults a battery
state-of-charge simulation (``repro.sim.energy.EnergySim``: solar input
masked by the eclipse series, idle draw, per-round FL activity billing).
Satellites below the SoC floor at selection time are ANDed out of the
contact-plan projection's validity mask — exactly like a satellite with no
remaining contact window — so they become zero-weight pad slots and the
fixed-shape dispatch never retraces. ``energy=None`` (the default) skips
every energy code path and is bitwise-identical to the pre-energy engine.

Reproduce the benchmark:
    PYTHONPATH=src python benchmarks/round_engine_perf.py \
        --out BENCH_round_engine.json
(the pre-change engine is retained in ``repro.core.round_engine_ref`` as
the golden-parity baseline).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregation import (apply_buffered_deltas,
                                    apply_stacked_deltas,
                                    make_robust_aggregator,
                                    quantized_weighted_average,
                                    robust_apply_buffered_deltas,
                                    stack_rows, weighted_average)
from repro.core.client import local_sgd_buffer, local_sgd_clients
from repro.core.contact_plan import ContactPlan
from repro.core.policy import PolicyInputs, resolve_policy, select_top
from repro.core.quantize import (quantize_roundtrip,
                                 quantize_roundtrip_stacked, transmit_bytes)
from repro.models.small import MODELS, accuracy
from repro.sim.energy import EnergyConfig, EnergySim
from repro.sim.events import (CLIENT_RETURN, ROUND_BARRIER, TRAIN_DONE,
                              EventQueue, WorldTimeline)
from repro.sim.faults import FaultConfig, FaultSim
from repro.sim.hardware import FleetProfile, HardwareProfile


@dataclasses.dataclass
class RoundRecord:
    """One completed FL round's bookkeeping (a ``SimResult`` is a list of
    these). ``energy_wh`` / ``skipped_low_power`` stay at their defaults
    when energy modeling is off (``FLConfig.energy is None``)."""
    round: int
    t_start: float
    t_end: float
    duration_s: float
    idle_s: float              # mean satellite idle time in the round
    comm_s: float              # mean communication time
    train_s: float             # mean on-board compute time
    accuracy: float
    participants: List[int]
    epochs: float = 0.0
    energy_wh: float = 0.0     # added FL energy billed this round (fleet sum)
    # orbit-eligible sats masked by the battery floor this round — a fleet
    # health gauge: it counts every masked candidate, whether or not the
    # cohort would have selected it
    skipped_low_power: int = 0
    # per-participant communication seconds {sat: s} — on a heterogeneous
    # fleet, slow-radio satellites show proportionally larger entries
    comm_s_by_sat: Dict[int, float] = dataclasses.field(default_factory=dict)
    # fault accounting (``FLConfig.faults``; all zeros when faults are off)
    skipped_faulted: int = 0       # outage-masked candidates + wiped/lost
                                   # updates this round
    dropped_contacts: int = 0      # transmission attempts lost to drops
    retransmit_bytes: float = 0.0  # bytes re-billed by retried transmissions
    # silent-corruption accounting: delivered updates whose payload was
    # SEU-corrupted or adversarially poisoned in flight (they still bill
    # their bytes — the radio delivered them — but carry bad weights), and
    # rows the robust aggregator attenuated/rejected this round
    corrupted_updates: int = 0
    clipped_updates: int = 0
    # graceful-degradation accounting (deadline/quorum rounds, bounded
    # retries, correlated storms; all zeros at the wait-for-all defaults)
    deadline_expired: int = 0      # 1 when the round closed at its deadline
                                   # with stragglers still in flight
    stragglers_carried: int = 0    # deliveries past the close: carried to a
                                   # later round as stale deltas under
                                   # late_policy="carry", dropped under
                                   # "discard" (the counter records the cut
                                   # either way)
    retries_exhausted: int = 0     # transmissions abandoned because the
                                   # retry budget ran out (never silent:
                                   # with max_retries=None a hard safety cap
                                   # still counts here instead of walking
                                   # the horizon)
    storm_events: int = 0          # correlated storms breaking this round
    # selection-policy accounting (``FLConfig.policy``; zeros/empty for
    # the built-in policies, which never defer or demote)
    policy_deferred: int = 0       # otherwise-eligible candidates the
                                   # policy deferred or demoted this round
                                   # (sum of policy_skips values)
    policy_skips: Dict[str, int] = dataclasses.field(default_factory=dict)
                                   # per-reason breakdown, e.g.
                                   # {"eclipse_deferred": 3} — hard skips
                                   # (energy_aware deferral/critical floor,
                                   # oracle doomed updates) and soft
                                   # demotions (deadline_aware storm/miss
                                   # penalties) both count


@dataclasses.dataclass
class FLConfig:
    """Knobs of the space-ified FL suite.

    Model / optimization
        ``model``: key in ``repro.models.small.MODELS`` ("cnn" | "mlp").
        ``epochs``: local epochs per round (E). FedAvg trains exactly E;
        FedProx treats E as the target and derives per-client budgets from
        the contact plan. ``batch_size`` / ``lr``: local SGD minibatch and
        step size. ``prox_mu``: FedProx proximal coefficient (ignored by
        FedAvg). ``min_epochs``: FedProxSchV2's floor — a client must fit
        at least this many epochs before its return contact or it is
        dropped from the round. ``max_local_epochs``: hard cap on orbit-
        derived budgets ("excessive epochs damage convergence", paper §6).

    Cohorts / rounds
        ``clients_per_round``: static cohort width C. The fixed-shape
        engine pads every round's dispatch to exactly C slots (unused
        slots get weight 0), so the trainer compiles once per config.
        ``buffer_size``: FedBuff's D — updates buffered before a flush.
        ``staleness_exponent``: FedBuff discount (1+staleness)^-a.
        ``max_rounds``: stop after this many rounds (or at horizon end).
        ``eval_every``: evaluate global accuracy every Nth round (other
        rounds carry the last value forward).

    Client selection
        ``selection``: "first_contact" (first C idle clients to reach a
        ground station), "scheduled" (FLSchedule, Alg. 5: smallest
        contact+return total), or "intra_sl" (FLIntraSL, Alg. 6: weights
        may return via any same-plane peer).
        ``policy``: the selection-policy layer (``repro.core.policy``).
        ``None`` (default) resolves to the built-in policy matching
        ``selection`` — guaranteed bitwise-identical to the pre-policy
        engine. A registered name ("first_contact" | "scheduled" |
        "intra_sl" | "deadline_aware" | "energy_aware" | "oracle") or a
        ``SelectionPolicy`` instance swaps in pluggable scoring +
        eligibility over the same batched projections: ``deadline_aware``
        demotes storm-exposed planes and projected deadline misses,
        ``energy_aware`` replaces the binary SoC floor with soft
        SoC-weighted scoring + sunlit-arc deferral (and drives FedBuff
        pickup deferral and AutoFLSat per-member epoch budgets), and
        ``oracle`` is the clairvoyant fault-resolved baseline. Note
        ``selection`` still controls the projection/return-route
        semantics; the policy only scores and gates.

    Transmission (QuAFL, PR 2)
        ``quant_bits``: 0 transmits float32; >0 quantizes every model
        crossing a link to that many bits per weight (per-tensor scale) —
        broadcasts are round-tripped through ``quantize_roundtrip`` so
        clients train on what the radio actually delivered, and link
        billing uses the compressed wire size. ``quant_kernel`` routes the
        server's dequantize+accumulate: "auto" (Pallas on TPU, jnp
        elsewhere) | "pallas" | "pallas_interpret" | "jnp".

    Energy
        ``energy``: ``repro.sim.energy.EnergyConfig`` enabling battery
        state-of-charge gating — satellites below the SoC floor at
        selection time are masked out (an extra eligibility mask on the
        contact-plan projection; the padded dispatch shape is unchanged,
        so nothing retraces) and each round bills the participants'
        training/radio energy. ``None`` (default) disables energy
        modeling entirely and is guaranteed bitwise-identical to the
        pre-energy engine.

    Faults (this PR)
        ``faults``: ``repro.sim.faults.FaultConfig`` enabling fault
        injection — seeded per-satellite outages (ANDed into the same
        eligibility mask as the energy gate; mask composition is
        commutative, see docs/ARCHITECTURE.md), per-contact transmission
        drops (retried at the next usable window with the bytes
        re-billed), radiation resets (local state wiped, in-flight update
        lost), and the optional IWQoS'23 energy-drain attack (requires
        ``energy`` — the attack drains batteries). ``None`` (default)
        disables every fault path and is bitwise-identical to the
        fault-free engine.

    Deadline / quorum rounds (graceful degradation)
        ``round_deadline_s``: with the default ``inf`` every synchronous
        round waits for its slowest participant (the PR 8 wait-for-all
        semantics, bitwise-unchanged). Finite: the round closes at
        ``t + round_deadline_s`` — stretched, if necessary, to the
        ``quorum``-th delivery, so a storm can delay a round but never
        starve the aggregate below ``quorum`` updates. Deliveries after
        the close are *stragglers*: zero weight this round, and under
        ``late_policy="carry"`` their updates are folded into a later
        round as FedBuff-style stale deltas (staleness-discounted by
        rounds elapsed); ``"discard"`` drops them outright. Applies to
        FedAvg/FedProx rounds and both AutoFLSat barrier tiers;
        FedBuffSat is already asynchronous and ignores the deadline.
        ``max_retries``: caps every drop-retry walk (sync downlink and
        AutoFLSat ISL chain) at that many retries with window-level
        exponential backoff; exhaustion is recorded in
        ``RoundRecord.retries_exhausted``. ``None`` keeps unbounded
        retries (modulo a hard safety cap — see ``_walk_drops``).

    Robust aggregation (this PR)
        ``aggregator``: ``None`` (default) keeps the exact legacy
        weighted-mean server — bitwise-identical to the pre-robust
        engine. A registry name ("norm_clip" | "trimmed_mean" |
        "median" | "krum") or a ``RobustAggregator`` instance swaps in
        a Byzantine-robust estimator over the stacked cohort (see
        ``repro.core.aggregation``): the defense against silently
        corrupted (``faults.corrupt_prob``) or poisoned
        (``faults.poison``) updates. With ``quant_bits > 0`` the cohort
        is first round-tripped through the QuAFL wire format, so the
        estimator sees exactly what the radio delivered; rank-based
        estimators route through the ``trimmed_agg`` Pallas kernel via
        the same ``quant_kernel`` mode knob.

    RNG convention: ``seed`` drives the JAX PRNG key stream for model
    init + minibatch order; ``faults.seed`` drives a *separate*
    ``np.random.default_rng`` stream for every fault draw (outages,
    resets, per-contact drops, payload corruption). The two streams
    never mix — enabling or reseeding faults never perturbs training
    randomness, and fault draws are counter-based per satellite/contact,
    so they are reproducible across engines and independent of query
    order.
    """
    model: str = "cnn"
    clients_per_round: int = 10          # C (static cohort width)
    epochs: int = 2                      # E (FedAvg; cap for FedProx)
    batch_size: int = 32
    lr: float = 0.05
    prox_mu: float = 0.01
    min_epochs: int = 0                  # FedProxSchV2 floor
    max_local_epochs: int = 30           # cap: "excessive epochs damage
                                         # convergence" (paper §6) + CPU cost
    buffer_size: int = 5                 # FedBuff D
    staleness_exponent: float = 0.5
    selection: str = "first_contact"     # | "scheduled" | "intra_sl"
    policy: Optional[object] = None      # selection policy: None (built-in
                                         # for `selection`, bitwise) | name |
                                         # SelectionPolicy instance
    quant_bits: int = 0                  # 0 => f32 transmission
    quant_kernel: str = "auto"           # quant_agg route: auto | pallas |
                                         # pallas_interpret | jnp
    max_rounds: int = 500
    seed: int = 0
    eval_every: int = 1
    energy: Optional[EnergyConfig] = None   # battery SoC gating (off = None)
    faults: Optional[FaultConfig] = None    # fault injection (off = None)
    aggregator: Optional[object] = None     # None => legacy weighted mean;
                                            # name | RobustAggregator instance
    round_deadline_s: float = float("inf")  # inf => wait-for-all rounds
    quorum: int = 1                # min deliveries before a deadline close
    late_policy: str = "carry"     # stragglers: "carry" (stale deltas) |
                                   # "discard"
    max_retries: Optional[int] = None   # drop-retry budget (None=unbounded)


def _model_tx_bytes(params, cfg: FLConfig) -> float:
    return transmit_bytes(params, cfg.quant_bits)


#: Hard safety cap on any drop-retry walk when ``max_retries`` is None.
#: ``drop_prob`` near 1 composed with outages used to walk the whole
#: horizon silently; a walk that somehow drops this many consecutive
#: passes is abandoned and *counted* (``retries_exhausted``), not hidden.
#: Unreachable under any realistic drop rate (0.9^1000 ~ 1e-46), so the
#: unbounded path stays bitwise-identical to the PR 7/8 engines.
_WALK_ATTEMPT_CAP = 1000

# ``lost`` codes of the drop-retry walks (truthy compatibility: the
# retained ref loops only test ``if lost:``)
_LOST_WINDOWS = 1      # horizon ran out of usable windows mid-walk
_LOST_RETRIES = 2      # the retry budget was exhausted


class SpaceifiedFL:
    """Shared machinery for the orbital suite."""

    name = "base"

    def __init__(self, plan: ContactPlan, hw, dataset, cfg: FLConfig):
        # the engine's spans and counters (repro.obs): its construction
        # here, its run() through obs.traced_run
        self.trace = obs.RunTrace(cfg.seed, self.name)
        with obs.recording(self.trace), obs.span("fl.init"):
            self._init(plan, hw, dataset, cfg)

    def _init(self, plan: ContactPlan, hw, dataset, cfg: FLConfig):
        # hw: HardwareProfile (uniform fleet), FleetProfile, or a
        # length-K profile sequence — timing always reads the fleet
        # arrays; self.hw stays the scalar primary profile for compat.
        self.fleet = FleetProfile.build(hw, plan.constellation.n_sats)
        self.hw = hw if isinstance(hw, HardwareProfile) else \
            self.fleet.primary
        self.plan, self.ds, self.cfg = plan, dataset, cfg
        key = jax.random.PRNGKey(cfg.seed)
        self.key, init_key = jax.random.split(key)
        init_fn, self.apply_fn = MODELS[cfg.model]
        img_shape = tuple(dataset.x.shape[2:])
        self.global_params = init_fn(init_key, img_shape, dataset.n_classes)
        self.tx_bytes = _model_tx_bytes(self.global_params, cfg)
        # (K,) per-satellite link times for the (fixed) wire size
        self._t_up_k = self.fleet.tx_time(self.tx_bytes, "uplink")
        self._t_down_k = self.fleet.tx_time(self.tx_bytes, "downlink")
        self._t_isl_k = self.fleet.tx_time(self.tx_bytes, "isl")
        self.records: List[RoundRecord] = []
        # per-kind discrete-event counts of the last run() (repro.sim.
        # events.EventStats); None until run() builds its timeline
        self.event_stats = None
        self._tx_cache = self._tx_cache_src = None
        # battery SoC gating (FLConfig.energy); None => engine is bitwise
        # identical to the pre-energy path (nothing below ever consults it)
        self.energy: Optional[EnergySim] = None
        # fault injection (FLConfig.faults); None => every fault branch
        # below is dead and the engine is bitwise-identical to fault-free
        self.faults: Optional[FaultSim] = None
        attack = None
        if cfg.faults is not None:
            if cfg.faults.attack is not None and cfg.energy is None:
                raise ValueError(
                    "FaultConfig.attack requires FLConfig.energy: the "
                    "energy-drain attack targets batteries")
            attack = cfg.faults.attack
            self.faults = FaultSim.for_plan(plan, cfg.faults)
        # Byzantine-robust server (FLConfig.aggregator); None => the exact
        # legacy weighted-mean path (guaranteed bitwise-identical)
        self.aggregator = make_robust_aggregator(cfg.aggregator)
        # selection-policy layer (FLConfig.policy); None resolves to the
        # built-in policy for cfg.selection — same scores, same masks,
        # same lexsort: bitwise-identical selection
        self.policy = resolve_policy(cfg.policy, cfg.selection)
        # per-reason skip counts of the last selection decision (the
        # RoundRecord.policy_skips source; {} for built-ins)
        self._policy_skips: Dict[str, int] = {}
        # deadline/quorum round semantics (graceful degradation). With the
        # inf default nothing below consults the deadline machinery and
        # rounds stay bitwise wait-for-all.
        if not cfg.round_deadline_s > 0.0:
            raise ValueError("FLConfig.round_deadline_s must be > 0 "
                             "(inf disables the deadline)")
        if cfg.quorum < 1:
            raise ValueError("FLConfig.quorum must be >= 1")
        if cfg.late_policy not in ("carry", "discard"):
            raise ValueError("FLConfig.late_policy must be 'carry' or "
                             f"'discard', got {cfg.late_policy!r}")
        if cfg.max_retries is not None and cfg.max_retries < 0:
            raise ValueError("FLConfig.max_retries must be >= 0 or None")
        self._deadline_on = bool(np.isfinite(cfg.round_deadline_s))
        # stragglers carried past a deadline close, folded into a later
        # round as stale deltas: (row_params, base_params, t_deliver,
        # round_picked, sat)
        self._carried: List[tuple] = []
        if cfg.energy is not None:
            # shared-fleet invariant: unless EnergyConfig.fleet overrides,
            # the battery bills the same per-satellite hardware that the
            # timing above schedules with
            self.energy = EnergySim.for_plan(plan, self.hw, cfg.energy,
                                             fleet=self.fleet.profiles,
                                             attack=attack)

    # -- timing helpers -------------------------------------------------
    def _t_up(self):
        return self.hw.tx_time(self.tx_bytes, "uplink")

    def _t_down(self):
        return self.hw.tx_time(self.tx_bytes, "downlink")

    # -- client selection (space-ification consideration 1 + augments) --
    def _projected_return(self, k: int, t: float, epochs: float):
        """(recv_end, train_end, ret_contact, relay) under current policy."""
        w = self.plan.next_contact(k, t)
        if w is None:
            return None
        recv_end = w[0] + self._t_up_k[k]
        train_end = recv_end + epochs * self.fleet.epoch_time_s[k]
        if self.cfg.selection == "intra_sl":
            ret = self.plan.next_cluster_contact(k, train_end)
            if ret is None:
                return None
            return (w, recv_end, train_end, (ret[0], ret[1], ret[2]), ret[3])
        ret = self.plan.next_contact(k, train_end)
        if ret is None:
            return None
        return (w, recv_end, train_end, ret, k)

    def _projected_returns(self, t: float, epochs: float, base=None):
        """Batched ``_projected_return`` over every satellite at once:
        one vectorized pass through the contact-plan arrays instead of K
        sequential Python projections. Returns a dict of (K,) arrays.

        ``base``: a projection dict this engine already computed at the
        SAME ``t`` (any epoch count). The first-contact query and the
        energy/fault masks depend only on ``t``, so they are reused
        verbatim — same arrays, bitwise — and only the epoch-dependent
        train-end + return-leg query re-runs. FedProx's floor projection
        rides this, halving its contact-plan passes per round."""
        plan = self.plan
        if base is None:
            avail, end, gs, valid = plan.next_contacts(t)
            recv_end = avail + self._t_up_k
        else:
            avail, end, gs = (base["contact_avail"], base["contact_end"],
                              base["contact_gs"])
            valid, recv_end = base["first_valid"], base["recv_end"]
        train_end = recv_end + self.fleet.train_time(epochs)
        if self.cfg.selection == "intra_sl":
            r_avail, r_end, r_gs, relay, r_valid = \
                plan.next_cluster_contacts(train_end)
        else:
            r_avail, r_end, r_gs, r_valid = plan.next_contacts(train_end)
            relay = np.arange(len(r_avail))
        orbit_valid = valid & r_valid
        if base is not None:
            energy_ok, fault_ok = base["energy_ok"], base["fault_ok"]
        elif self.energy is not None:
            # battery gating: SoC at selection time must clear the floor.
            # advance_to is idempotent at equal t, so the repeated
            # projections FedProx makes within one round stay consistent.
            self.energy.advance_to(float(t))
            energy_ok = self.energy.eligible()
        else:
            energy_ok = np.ones(len(orbit_valid), bool)
        if base is None:
            if self.faults is not None:
                # outage gating: a satellite inside a fault outage at
                # selection time is masked exactly like one below the
                # battery floor — boolean AND into the same validity mask
                # (composition order is immaterial), zero-weight pad
                # slot, no retracing.
                fault_ok = self.faults.available(t)
            else:
                fault_ok = np.ones(len(orbit_valid), bool)
        return {"contact_avail": avail, "contact_end": end, "contact_gs": gs,
                "recv_end": recv_end, "train_end": train_end,
                "ret_avail": r_avail, "ret_end": r_end, "ret_gs": r_gs,
                "relay": relay, "valid": orbit_valid & energy_ok & fault_ok,
                "orbit_valid": orbit_valid, "energy_ok": energy_ok,
                "fault_ok": fault_ok, "first_valid": valid}

    def _policy_inputs(self, proj, t: float, epochs: float) -> PolicyInputs:
        """Bundle the batched score inputs for the selection policy."""
        return PolicyInputs(t=float(t), epochs=float(epochs), proj=proj,
                            fleet=self.fleet, t_up_k=self._t_up_k,
                            t_down_k=self._t_down_k,
                            clients_per_round=self.cfg.clients_per_round,
                            round_deadline_s=self.cfg.round_deadline_s,
                            energy=self.energy, faults=self.faults,
                            engine=self)

    def _select_from_projections(self, proj, t: Optional[float] = None,
                                 epochs: Optional[float] = None
                                 ) -> List[int]:
        """Policy-layer selection over a batched projection: the policy
        scores + gates the fleet, ``select_top`` picks the lowest
        ``clients_per_round`` scores with the (score, sat-index)
        tie-break. The built-in policies reproduce the pre-policy
        branches bitwise (same arrays, same lexsort). The decision's
        per-reason skip counts are stashed on ``_policy_skips`` for the
        round record."""
        cfg = self.cfg
        if t is None:
            # legacy single-arg call (retained ref engines subclass this):
            # the projection was taken at cfg.epochs from the selection
            # clock; only contact_avail-relative scores use t, and every
            # shipped policy scores on absolute projection times, so the
            # round start is recoverable from the projection itself
            t = float(np.min(proj["contact_avail"]))
        decision = self.policy.decide(
            self._policy_inputs(proj, t, cfg.epochs
                                if epochs is None else epochs))
        self._policy_skips = {k: int(v) for k, v in decision.skips.items()
                              if v}
        return select_top(decision.score, decision.eligible,
                          cfg.clients_per_round)

    def select_clients(self, t: float) -> List[int]:
        return self._select_from_projections(
            self._projected_returns(t, self.cfg.epochs), t)

    # -- transmission (live QuAFL wire format) ---------------------------
    def _tx_global(self):
        """The global model as the clients receive it over the uplink
        (memoized per global-params version: FedBuff picks it up once per
        event, so the round-trip must not be recomputed while the global
        is unchanged)."""
        if not self.cfg.quant_bits:
            return self.global_params
        if self._tx_cache_src is not self.global_params:
            self._tx_cache = quantize_roundtrip(self.global_params,
                                                self.cfg.quant_bits)
            self._tx_cache_src = self.global_params
        return self._tx_cache

    def _aggregate(self, stacked, weights):
        """Server-side aggregation of a returned (stacked) cohort.
        Returns ``(params, n_attenuated)`` — the robust estimator's
        attenuated/rejected row count, 0 on the plain mean paths.

        With quantization on, the plain path dequantizes + accumulates
        through the quant_agg kernel; the robust path first round-trips
        the cohort through the QuAFL wire format so the estimator sees
        exactly what the radio delivered, then routes rank-based
        defenses through the trimmed_agg kernel (same mode knob)."""
        if self.aggregator is not None:
            if self.cfg.quant_bits:
                stacked = quantize_roundtrip_stacked(stacked,
                                                     self.cfg.quant_bits)
            return self.aggregator.aggregate(stacked, weights,
                                             self._tx_global(),
                                             mode=self.cfg.quant_kernel)
        if self.cfg.quant_bits:
            return quantized_weighted_average(
                stacked, weights, self.cfg.quant_bits,
                mode=self.cfg.quant_kernel), 0
        return weighted_average(stacked, weights), 0

    # -- fixed-shape training dispatch -----------------------------------
    def _train_cohort(self, sel: List[int], epochs, prox: bool = False):
        """Train ``sel`` inside a padded cohort of static width
        ``cfg.clients_per_round``.

        Pad slots replay client 0 with a dummy key and get weight 0, so
        they vanish from the aggregate; the dispatch shape never changes,
        so the trainer compiles once per configuration. Returns
        (stacked trained params (W, ...), aggregation weights (W,))."""
        with obs.span("fl.train"):
            cfg = self.cfg
            W, m = cfg.clients_per_round, len(sel)
            ks = jax.random.split(self.key, m + 1)
            self.key = ks[0]
            ks = obs.sync(ks)          # the client keys, read back once
            obs.count("cohort_pad_slots", W - m)
            keys = np.empty((W,) + ks.shape[1:], dtype=ks.dtype)
            keys[:m] = ks[1:]
            keys[m:] = keys[0]
            idx = np.zeros(W, np.int64)
            idx[:m] = sel
            ep = np.ones(W, np.int32)
            ep[:m] = epochs
            tx_global = self._tx_global()
            stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (W,) + p.shape), tx_global)
            gather = jnp.asarray(idx)
            trained = local_sgd_clients(
                cfg.model, stacked, self.ds.x[gather], self.ds.y[gather],
                jnp.asarray(keys), ep, cfg.batch_size, cfg.lr,
                mu=cfg.prox_mu if prox else 0.0,
                global_params=tx_global if prox else None)
            n_k = np.zeros(W, np.float64)
            n_k[:m] = self.ds.n_per_client
            return trained, n_k

    # -- fault resolution ------------------------------------------------
    def _next_available_contact(self, k: int, t: float):
        """``plan.next_contact`` that skips windows the satellite spends
        inside a fault outage (plain ``next_contact`` when faults — or
        outages — are off, so the fault-free path is untouched). A window
        whose outage ends mid-window starts late at the recovery time."""
        if not np.isfinite(t):
            return None
        if self.faults is None or not self.faults.cfg.has_outages:
            return self.plan.next_contact(k, t)
        tq = float(t)
        while True:
            w = self.plan.next_contact(k, tq)
            if w is None:
                return None
            up = float(self.faults.next_up(np.array([k]),
                                           np.array([w[0]]))[0])
            if up <= w[0]:
                return w
            if up < w[1]:
                return (up, w[1], w[2])
            tq = up                 # strictly past w[0]: walk terminates

    def _walk_drops(self, k: int, w_first):
        """Drop-retry walk of ``k``'s downlink from the usable window
        ``w_first`` (a ``(t_avail, end, gs)`` tuple): the drop draw is
        the seeded fate of the whole pass, so a dropped attempt spends
        its airtime and re-acquires at the *next* usable pass — never
        microseconds later inside the same one (per-airtime retries would
        turn one dropped pass into millions of fresh draws on a fast
        link, and the walk keys a new RNG per draw). Returns ``(t_done,
        drops, rebill_bytes, lost)`` — ``drops`` counts lost passes,
        ``rebill_bytes`` bills every attempt beyond the first, and
        ``lost`` is 0 (delivered), ``_LOST_WINDOWS`` (the horizon ran out
        of usable windows) or ``_LOST_RETRIES`` (the attempt budget ran
        out: ``cfg.max_retries`` retries when set, else the
        ``_WALK_ATTEMPT_CAP`` safety cap — a storm pinning ``drop_prob``
        near 1 must surface as counted exhaustion, not as a silent walk
        to the horizon). Both lost codes are truthy, so the retained ref
        loops' ``if lost:`` checks are unchanged.

        With ``max_retries`` set, retry ``j`` backs off window-level
        exponentially: it skips ``2**(j-1) - 1`` additional usable passes
        before re-keying the radio (shift clamped at 16), modelling a
        link-layer that stops hammering a stormy channel. Unbounded mode
        performs no backoff — the PR 7 walk, bitwise."""
        t_down = float(self._t_down_k[k])
        bounded = self.cfg.max_retries is not None
        budget = self.cfg.max_retries if bounded else _WALK_ATTEMPT_CAP
        w, drops = w_first, 0
        while self.faults.contact_dropped(k, float(w[0])):
            drops += 1
            if drops > budget:
                return (float(w[0]) + t_down, drops,
                        max(drops - 1, 0) * self.tx_bytes, _LOST_RETRIES)
            nxt = self._next_available_contact(
                k, max(float(w[0]) + t_down, float(w[1])))
            if bounded:
                for _ in range((1 << min(drops - 1, 16)) - 1):
                    if nxt is None:
                        break
                    nxt = self._next_available_contact(k, float(nxt[1]))
            if nxt is None:
                return (float(w[0]) + t_down, drops,
                        max(drops - 1, 0) * self.tx_bytes, _LOST_WINDOWS)
            w = nxt
        return float(w[0]) + t_down, drops, drops * self.tx_bytes, 0

    def _faulted_return_legs(self, ks, recv_end, train_end, ends, comms):
        """Re-resolve the selected cohort's return downlinks under faults
        (sync engines; only called when ``self.faults`` is set).

        Per client: the first *usable* return window at/after train end
        (outages can push it past the fault-free projection), then the
        drop-retry walk, then the radiation check — a reset anywhere in
        (recv_end, delivery] wipes the update. Billing rules (documented
        in docs/ARCHITECTURE.md): a delivered update with d drops bills
        uplink + (d+1) downlinks and re-bills d×tx_bytes; a client whose
        windows run out mid-walk bills the d attempts that really keyed
        the radio; a wiped client bills its uplink only (the reset, not
        the radio, lost the update). Every non-delivered client
        contributes aggregation weight 0.

        Returns ``(delivered (m,) 0/1 floats, ends, comms, n_faulted,
        drops, rebill_bytes, n_retries_exhausted)`` with
        ``ends``/``comms`` updated copies."""
        m = len(ks)
        delivered = np.ones(m)
        ends, comms = ends.copy(), comms.copy()
        n_faulted, drops_total, rebill_total, n_rex = 0, 0, 0.0, 0
        check_resets = self.faults.cfg.has_resets
        for i in range(m):
            k = int(ks[i])
            t_up = float(self._t_up_k[k])
            w0 = self._next_available_contact(k, float(train_end[i]))
            if w0 is None:          # outages outlast every return window
                delivered[i], n_faulted = 0.0, n_faulted + 1
                ends[i], comms[i] = float(train_end[i]), t_up
                continue
            t_done, d, rb, lost = self._walk_drops(k, w0)
            if lost:
                delivered[i], n_faulted = 0.0, n_faulted + 1
                if lost == _LOST_RETRIES:
                    n_rex += 1
                ends[i], comms[i] = t_done, t_up + d * float(
                    self._t_down_k[k])
                drops_total += d
                rebill_total += rb
                continue
            if check_resets and self.faults.reset_in(
                    k, float(recv_end[i]), t_done):
                delivered[i], n_faulted = 0.0, n_faulted + 1
                ends[i], comms[i] = t_done, t_up
                continue
            ends[i] = t_done
            comms[i] += d * float(self._t_down_k[k])
            drops_total += d
            rebill_total += rb
        return (delivered, ends, comms, n_faulted, drops_total, rebill_total,
                n_rex)

    def _selection_faulted(self, proj) -> int:
        """Candidates masked *only* by an outage at selection time."""
        if self.faults is None:
            return 0
        return int(np.sum(proj["orbit_valid"] & proj["energy_ok"]
                          & ~proj["fault_ok"]))

    # -- deadline/quorum round close (graceful degradation) ---------------
    def _close_round(self, t: float, ends, delivered):
        """Round-close policy over the participants' delivery times.

        Returns ``(t_close, on_time, expired)``. With the deadline off
        (``round_deadline_s=inf``) ``t_close`` is the natural
        wait-for-all end — the latest *delivered* end, or the latest end
        when nothing delivered — with ``on_time == delivered`` and
        ``expired=False``: bitwise-identical to the PR 8 engines. With a
        finite deadline the round closes at
        ``max(t + round_deadline_s, quorum-th delivery)``: the deadline
        cuts the slow tail, but never before ``cfg.quorum`` deliveries
        have landed, so a storm can delay a round yet never starve the
        aggregate below the quorum. A delivery after ``t_close`` is a
        straggler (``on_time`` False); if every delivery makes the
        deadline the close is the natural end and nothing expired."""
        delivered = np.asarray(delivered, bool)
        natural = float(ends[delivered].max() if delivered.any()
                        else ends.max())
        if not self._deadline_on:
            return natural, delivered, False
        t_deadline = t + self.cfg.round_deadline_s
        if natural <= t_deadline or not delivered.any():
            return natural, delivered, False
        times = np.sort(ends[delivered])
        q = min(self.cfg.quorum, len(times))
        t_close = max(t_deadline, float(times[q - 1]))
        if t_close >= natural:
            return natural, delivered, False
        return t_close, delivered & (ends <= t_close), True

    def _carry_straggler(self, trained, i: int, base, t_deliver: float,
                         r: int, sat: int) -> None:
        """Bank row ``i`` of a stacked trained cohort as a straggler:
        its update (and the broadcast ``base`` it trained from) is folded
        into a later round once the clock passes its delivery time."""
        row = jax.tree.map(lambda p: p[i], trained)
        self._carried.append((row, base, float(t_deliver), int(r), int(sat)))

    def _fold_carried(self, t_close: float, r: int) -> int:
        """Fold every carried straggler whose delivery time has passed
        into the global model as FedBuff-style stale deltas:
        ``global += mean_j w_j * (row_j - base_j)`` with the staleness
        discount ``w_j = (1 + r - r_orig)**(-staleness_exponent)`` —
        exactly the async engine's discount, applied at the first round
        close at/after the straggler's delivery. Routed through the
        robust estimator when one is configured. Returns the number of
        stragglers folded (the rest stay banked)."""
        if not self._carried:
            return 0
        due = [c for c in self._carried if c[2] <= t_close]
        if not due:
            return 0
        self._carried = [c for c in self._carried if c[2] > t_close]
        self._apply_deltas(
            [c[:2] for c in due],
            [(1.0 + max(r - c[3], 0)) ** (-self.cfg.staleness_exponent)
             for c in due])
        return len(due)

    def _apply_deltas(self, rows, weights) -> int:
        """``global += combine_k weights[k] * (new_k - base_k)`` over
        ``rows`` of ``(new_k, base_k)`` models: the weighted mean in one
        program (``apply_buffered_deltas``), or the robust estimator when
        one is configured. Returns the estimator's attenuated row count
        (0 for the mean). The straggler fold."""
        wgts = np.asarray(weights, np.float32)
        if self.aggregator is None:
            self.global_params = apply_buffered_deltas(
                self.global_params, rows, wgts)
            return 0
        return self._robust_deltas(*stack_rows(rows), wgts)

    def _apply_stacked_deltas(self, stacked_new, bases, weights) -> int:
        """``_apply_deltas`` over the stacked (D, ...) new models and the
        sequence of their ``bases`` (``apply_stacked_deltas``). The
        FedBuff flush."""
        wgts = np.asarray(weights, np.float32)
        if self.aggregator is None:
            self.global_params = apply_stacked_deltas(
                self.global_params, stacked_new, bases, wgts)
            return 0
        return self._robust_deltas(stacked_new, stack_rows(bases), wgts)

    def _robust_deltas(self, stacked_new, stacked_base, wgts) -> int:
        # the estimator sees the staleness-weighted deltas (zero
        # reference), so a poisoned or corrupted row is attenuated
        # before it touches the global
        self.global_params, n_att = robust_apply_buffered_deltas(
            self.global_params, stacked_new, stacked_base, wgts,
            self.aggregator, mode=self.cfg.quant_kernel)
        return n_att

    def _storms_in(self, t_from: float, t_to: float) -> int:
        """Correlated storms breaking in ``(t_from, t_to]`` (0 when
        faults or storms are off) — ``RoundRecord.storm_events``."""
        if self.faults is None:
            return 0
        return self.faults.storms_between(t_from, t_to)

    # -- silent payload faults (SEU corruption + poisoning) --------------
    def _corrupt_row(self, params, i: int, k: int, t_deliver: float,
                     reference):
        """Apply ``k``'s payload fault (if any) to row ``i`` of a stacked
        pytree delivered at ``t_deliver``. Returns (params, was_bad).

        A compromised satellite (``faults.poison``) submits the
        model-replacement payload ``(1+s)*ref - s*trained`` — its honest
        delta reversed and amplified by ``s`` — crafted from the
        ``reference`` it trained against (one model: the broadcast global,
        or the base a FedBuff return picked up), so poisoning takes
        precedence over the SEU draw. Otherwise a counter-based SEU draw
        (``corruption_at``) may flip the row's sign, blow up its scale,
        or add large-magnitude seeded noise. Only this row of the tree is
        touched: corruption must never perturb the other cohort members.
        """
        fc = self.faults.cfg
        if fc.poison is not None and fc.poison.compromised(k):
            s = fc.poison.scale
            params = jax.tree.map(
                lambda p, g: p.at[i].set(
                    ((1.0 + s) * g.astype(jnp.float32)
                     - s * p[i].astype(jnp.float32)).astype(p.dtype)),
                params, reference)
            return params, True
        draw = self.faults.corruption_at(k, t_deliver)
        if draw is None:
            return params, False
        mode, factor, noise_seed = draw
        if mode == "sign_flip":
            params = jax.tree.map(lambda p: p.at[i].multiply(-1.0), params)
        elif mode == "scale":
            params = jax.tree.map(lambda p: p.at[i].multiply(factor), params)
        else:                           # large-magnitude seeded noise
            rng = np.random.default_rng(noise_seed)
            params = jax.tree.map(
                lambda p: p.at[i].add(jnp.asarray(
                    rng.standard_normal(p.shape[1:]) * factor, p.dtype)),
                params)
        return params, True

    def _apply_payload_faults(self, trained, sel, delivered, t_deliver):
        """Corrupt/poison the *delivered* rows of a trained cohort at
        their delivery times (sync engines). Non-delivered rows carry
        weight 0 and are skipped — a lost update cannot also be
        corrupted. Returns (trained, n_corrupted). Callers gate on
        ``faults.cfg.has_payload_faults`` so the zero-rate path never
        rebuilds the tree."""
        ref = self._tx_global()
        n_corr = 0
        for i, k in enumerate(sel):
            if delivered is not None and not delivered[i] > 0:
                continue
            trained, bad = self._corrupt_row(trained, i, int(k),
                                             float(t_deliver[i]), ref)
            n_corr += int(bad)
        return trained, n_corr

    # -- energy accounting ----------------------------------------------
    def _post_recovery_contact(self, k: int, t: float):
        """Stand-down policy for a drained satellite: its earliest GS
        contact at/after battery recovery (idle + solar only), or None if
        the battery never clears the floor. Fault-aware: the post-recovery
        contact must also fall outside any outage."""
        rt = self.energy.recover_time(k)
        return None if rt is None else \
            self._next_available_contact(k, max(rt, t))

    def _round_energy(self, proj, ks, trains, comms, t_round_end):
        """Advance the fleet's batteries to the round end (idle draw +
        solar input for everyone) and bill the participants' added FL
        energy. Returns (energy_wh, skipped_low_power) — (0.0, 0) when
        energy modeling is off."""
        if self.energy is None:
            return 0.0, 0
        skipped = int(np.sum(proj["orbit_valid"] & ~proj["energy_ok"]))
        self.energy.advance_to(t_round_end)
        return self.energy.bill_activity(ks, trains, comms), skipped

    # -- evaluation ------------------------------------------------------
    def evaluate(self) -> float:
        with obs.span("fl.evaluate"):
            return accuracy(self.apply_fn, self.global_params,
                            self.ds.x_test, self.ds.y_test)

    # -- main loop (discrete-event core) ---------------------------------
    @obs.traced_run
    def run(self, t0: float = 0.0, t_end: Optional[float] = None,
            max_rounds: Optional[int] = None):
        """Event-driven main loop. ROUND_BARRIER decision events on a
        deterministic :class:`~repro.sim.events.EventQueue` fire
        ``run_round`` at exactly the clock points the retained per-round
        loop used (``repro.core.round_loop_ref.run_sync_loop`` — the
        golden baseline; ``tests/test_event_parity.py`` gates the
        ``RoundRecord`` streams bitwise across the scenario matrix). The
        world events between decision points — contact window open/close,
        eclipse transitions, fault outages/recoveries, radiation resets —
        resolve in one batched ``WorldTimeline.advance_through`` pass per
        round instead of per-event Python stepping; battery-floor
        crossings are noted by diffing the gating mask at each barrier.
        ``self.event_stats`` holds the per-kind counts afterwards, and
        ``self.trace`` the run's spans and counters (``repro.obs``)."""
        t_end = t_end if t_end is not None else self.plan.horizon_s
        max_rounds = max_rounds or self.cfg.max_rounds
        queue = EventQueue()
        queue.push(t0, ROUND_BARRIER)
        timeline = WorldTimeline.for_fl(self.plan, self.energy, self.faults)
        self.event_stats = self.trace.events = st = timeline.stats
        r = 0
        while queue and r < max_rounds:
            ev = queue.pop()
            if ev.t >= t_end:
                break
            st.add(ROUND_BARRIER)
            with obs.span("fl.round"):
                rec = self.run_round(r, ev.t)
                if rec is None:
                    break
                self.records.append(rec)
                timeline.advance_through(rec.t_end)
                st.add(TRAIN_DONE, len(rec.participants))
                if self.energy is not None:
                    timeline.note_eligibility(self.energy.eligible(),
                                              rec.t_end)
                queue.push(rec.t_end, ROUND_BARRIER)
            r += 1
            self.trace.round_done()
        return self.records

    def run_round(self, r: int, t: float) -> Optional[RoundRecord]:
        raise NotImplementedError


class FedAvgSat(SpaceifiedFL):
    """Algorithm 1 (+ FLSchedule / FLIntraSL via cfg.selection)."""

    name = "fedavg"

    def run_round(self, r, t):
        cfg = self.cfg
        with obs.span("fl.select"):
            proj = self._projected_returns(t, cfg.epochs)
            sel = self._select_from_projections(proj, t)
        pol_skips = self._policy_skips
        if not sel:
            return None
        # train selected clients (padded cohort, same epoch count:
        # synchronous)
        trained, n_k = self._train_cohort(sel, cfg.epochs)

        ks = np.asarray(sel)
        ends = proj["ret_avail"][ks] + self._t_down_k[ks]
        # clamp like FedProxSat: a return window already open at train end
        # means zero idle, not negative idle
        idles = (proj["contact_avail"][ks] - t) \
            + np.maximum(proj["ret_avail"][ks] - proj["train_end"][ks], 0.0)
        comms = self._t_up_k[ks] + self._t_down_k[ks]
        trains = proj["train_end"][ks] - proj["recv_end"][ks]
        n_flt, drops, rebill, n_corr, n_clip, n_rex = 0, 0, 0.0, 0, 0, 0
        delivered = np.ones(len(sel))
        if self.faults is not None:
            delivered, ends, comms, n_flt, drops, rebill, n_rex = \
                self._faulted_return_legs(ks, proj["recv_end"][ks],
                                          proj["train_end"][ks], ends, comms)
            n_k[:len(sel)] *= delivered    # lost/wiped updates: weight 0
            n_flt += self._selection_faulted(proj)
            if self.faults.cfg.has_payload_faults:
                # corrupt/poison delivered rows at their delivery times —
                # the bytes were billed above; only the weights went bad
                trained, n_corr = self._apply_payload_faults(
                    trained, sel, delivered, ends)
        # the server waits for deliveries — until the deadline/quorum
        # close cuts the slow tail (wait-for-all, bitwise, at inf)
        t_round_end, on_time, expired = self._close_round(
            t, ends, delivered > 0)
        n_exp, n_strag = 0, 0
        if expired:
            n_exp = 1
            late = np.nonzero((delivered > 0) & ~on_time)[0]
            n_strag = len(late)
            if cfg.late_policy == "carry" and n_strag:
                base_ref = self._tx_global()   # the broadcast they trained on
                for i in late:
                    self._carry_straggler(trained, int(i), base_ref,
                                          float(ends[int(i)]), r,
                                          int(sel[int(i)]))
            n_k[:len(sel)] *= on_time.astype(np.float64)
        with obs.span("fl.aggregate"):
            if float(n_k.sum()) > 0.0:     # always true when faults are off
                self.global_params, n_clip = self._aggregate(trained, n_k)
            if self._carried:
                self._fold_carried(t_round_end, r)
        wh, skipped = self._round_energy(proj, ks, trains, comms, t_round_end)
        acc = self.evaluate() if r % cfg.eval_every == 0 else \
            (self.records[-1].accuracy if self.records else 0.0)
        return RoundRecord(r, t, t_round_end, t_round_end - t,
                           float(np.mean(idles)), float(np.mean(comms)),
                           float(np.mean(trains)), acc, sel,
                           epochs=cfg.epochs, energy_wh=wh,
                           skipped_low_power=skipped,
                           comm_s_by_sat=dict(zip(sel, comms.tolist())),
                           skipped_faulted=n_flt, dropped_contacts=drops,
                           retransmit_bytes=rebill, corrupted_updates=n_corr,
                           clipped_updates=n_clip, deadline_expired=n_exp,
                           stragglers_carried=n_strag,
                           retries_exhausted=n_rex,
                           storm_events=self._storms_in(t, t_round_end),
                           policy_deferred=sum(pol_skips.values()),
                           policy_skips=pol_skips)


class FedProxSat(SpaceifiedFL):
    """Algorithm 3: partial updates — each client trains until it reaches a
    ground station; a proximal term bounds local drift. V2 (min_epochs>0)
    enforces a minimum-epoch floor before returning (paper §5.1.1).

    Per-client epoch budgets come from ONE batched floor projection over
    the contact plan; a selected client whose floor-epoch return contact
    never materializes is dropped from the round (the round only fails if
    nobody can return)."""

    name = "fedprox"

    def run_round(self, r, t):
        cfg = self.cfg
        with obs.span("fl.select"):
            proj = self._projected_returns(t, cfg.epochs)
            sel = self._select_from_projections(proj, t)
            pol_skips = self._policy_skips
            if not sel:
                return None
            floor_ep = max(cfg.min_epochs, 1)
            # ONE contact-plan pass per round: the floor projection reuses
            # the selection projection's first-contact query + energy/
            # fault masks (identical at the same t — bitwise), re-running
            # only the epoch-dependent return leg; when the floor equals
            # the selection epoch count the projections coincide entirely.
            projf = proj if floor_ep == cfg.epochs else \
                self._projected_returns(t, floor_ep, base=proj)
            # refilter under the floor projection through the policy's
            # eligibility (for the built-ins this IS projf["valid"] — the
            # exact pre-policy refilter)
            floor_ok = self.policy.decide(
                self._policy_inputs(projf, t, floor_ep)).eligible
            sel = [k for k in sel if floor_ok[k]]
        if not sel:
            return None
        ks = np.asarray(sel)
        recv_end = projf["recv_end"][ks]
        ep = np.clip(((projf["ret_avail"][ks] - recv_end)
                      // self.fleet.epoch_time_s[ks]).astype(np.int64),
                     floor_ep, cfg.max_local_epochs).astype(np.int32)
        train_end = recv_end + self.fleet.epoch_time_s[ks] * ep
        trained, n_k = self._train_cohort(sel, ep, prox=True)

        ends = projf["ret_avail"][ks] + self._t_down_k[ks]
        idles = (projf["contact_avail"][ks] - t) \
            + np.maximum(projf["ret_avail"][ks] - train_end, 0.0)
        comms = self._t_up_k[ks] + self._t_down_k[ks]
        trains = train_end - recv_end
        n_flt, drops, rebill, n_corr, n_clip, n_rex = 0, 0, 0.0, 0, 0, 0
        delivered = np.ones(len(sel))
        if self.faults is not None:
            # epoch budgets keep the fault-free projection (the client
            # cannot foresee faults); only the return leg is re-resolved
            delivered, ends, comms, n_flt, drops, rebill, n_rex = \
                self._faulted_return_legs(ks, recv_end, train_end,
                                          ends, comms)
            n_k[:len(sel)] *= delivered
            n_flt += self._selection_faulted(projf)
            if self.faults.cfg.has_payload_faults:
                trained, n_corr = self._apply_payload_faults(
                    trained, sel, delivered, ends)
        t_round_end, on_time, expired = self._close_round(
            t, ends, delivered > 0)
        n_exp, n_strag = 0, 0
        if expired:
            n_exp = 1
            late = np.nonzero((delivered > 0) & ~on_time)[0]
            n_strag = len(late)
            if cfg.late_policy == "carry" and n_strag:
                base_ref = self._tx_global()
                for i in late:
                    self._carry_straggler(trained, int(i), base_ref,
                                          float(ends[int(i)]), r,
                                          int(sel[int(i)]))
            n_k[:len(sel)] *= on_time.astype(np.float64)
        with obs.span("fl.aggregate"):
            if float(n_k.sum()) > 0.0:
                self.global_params, n_clip = self._aggregate(trained, n_k)
            if self._carried:
                self._fold_carried(t_round_end, r)
        wh, skipped = self._round_energy(projf, ks, trains, comms,
                                         t_round_end)
        acc = self.evaluate() if r % cfg.eval_every == 0 else \
            (self.records[-1].accuracy if self.records else 0.0)
        return RoundRecord(r, t, t_round_end, t_round_end - t,
                           float(np.mean(idles)), float(np.mean(comms)),
                           float(np.mean(trains)), acc, sel,
                           epochs=float(np.mean(ep)), energy_wh=wh,
                           skipped_low_power=skipped,
                           comm_s_by_sat=dict(zip(sel, comms.tolist())),
                           skipped_faulted=n_flt, dropped_contacts=drops,
                           retransmit_bytes=rebill, corrupted_updates=n_corr,
                           clipped_updates=n_clip, deadline_expired=n_exp,
                           stragglers_carried=n_strag,
                           retries_exhausted=n_rex,
                           storm_events=self._storms_in(t, t_round_end),
                           policy_deferred=sum(pol_skips.values()),
                           policy_skips=pol_skips)


class FedBuffSat(SpaceifiedFL):
    """Algorithm 4: asynchronous buffered aggregation. Clients train
    continuously between ground contacts (near-zero idle, paper Fig. 5c);
    the server folds in updates with staleness discounting and completes a
    "round" when the buffer reaches D updates. A return launches no
    device work: it queues a pending entry, and the flush trains the
    whole buffer in one program (``local_sgd_buffer``) and folds it in
    one stacked delta reduction (``apply_stacked_deltas``).

    This is the discrete-event core's first real consumer: the pending
    deliveries live on a deterministic ``EventQueue`` of CLIENT_RETURN
    events ordered ``(t, priority, sat, seq)`` — at a timestamp tie two
    clients pop in satellite-index order, matching (and now guaranteeing
    by contract) the retained heap's ``(t, k)`` tuple comparison. The
    pre-event-engine loop is kept verbatim in
    ``repro.core.round_loop_ref.run_fedbuff_loop`` as the golden parity
    baseline."""

    name = "fedbuff"

    # robust-estimator row count and corrupted-payload count of the last
    # buffer flush (read by both loops, which share the flush)
    _last_flush_clipped = 0
    _last_flush_corrupted = 0

    def _flush_buffer(self, buf) -> None:
        """Train a full buffer and fold it into the global model.

        ``buf`` holds one pending entry per return,
        ``(k, base, epochs, weight, t_ret)``: the satellite, the version
        it picked up, its epoch budget, its staleness weight and its
        return time. One program trains every entry
        (``local_sgd_buffer``), drawing each entry's key from
        ``self.key`` in return order, as a split at each return would.
        The trained content never depends on the return time, so the
        training can wait for the flush, the only place that reads it.
        What crosses the radio then happens to the stacked rows in
        return order: the QuAFL round trip (``quant_bits``), then the
        payload faults at each return's time. One stacked delta
        reduction folds the buffer, through the robust estimator when
        ``FLConfig.aggregator`` is set.

        Shared by the event-driven ``run()`` and
        ``round_loop_ref.run_fedbuff_loop``: like ``round_engine_ref``
        shares ``weighted_average``, sharing the flush keeps the
        bitwise-parity gate about the *clock*, not the training or the
        reduction tree. A partial buffer left when a run ends is never
        trained, so ``self.key`` then ends fewer splits on than a
        per-return split would leave it; nothing reads it after
        ``run()``. Sets ``self._last_flush_clipped`` and
        ``self._last_flush_corrupted``."""
        cfg = self.cfg
        sats = np.array([b[0] for b in buf], np.int32)
        bases = tuple(b[1] for b in buf)
        with obs.span("fl.train"):
            obs.count("fedbuff_buffered_trains", len(buf))
            trained, self.key = local_sgd_buffer(
                cfg.model, bases, self.ds.x, self.ds.y, sats, self.key,
                np.array([b[2] for b in buf], np.int32), cfg.batch_size,
                cfg.lr, cfg.prox_mu)
        with obs.span("fl.aggregate"):
            if cfg.quant_bits:          # the returned models cross the radio
                trained = quantize_roundtrip_stacked(trained, cfg.quant_bits)
            n_corr = 0
            if self.faults is not None and self.faults.cfg.has_payload_faults:
                # a payload may be corrupted or poisoned in flight; the
                # reference is the version the client trained from
                for i, (k, base, _, _, t_ret) in enumerate(buf):
                    trained, bad = self._corrupt_row(trained, i, int(k),
                                                     t_ret, base)
                    n_corr += int(bad)
            self._last_flush_corrupted = n_corr
            self._last_flush_clipped = self._apply_stacked_deltas(
                trained, bases, [b[3] for b in buf])

    @obs.traced_run
    def run(self, t0: float = 0.0, t_end: Optional[float] = None,
            max_rounds: Optional[int] = None):
        cfg, plan = self.cfg, self.plan
        t_end = t_end if t_end is not None else plan.horizon_s
        max_rounds = max_rounds or cfg.max_rounds
        K = plan.constellation.n_sats

        ep_s = self.fleet.epoch_time_s            # (K,) per-satellite
        # pending deliveries live on the deterministic event clock; world
        # events (contacts, eclipses, outages, resets) resolve batched on
        # the timeline between pops
        queue = EventQueue()
        timeline = WorldTimeline.for_fl(self.plan, self.energy, self.faults)
        self.event_stats = self.trace.events = st = timeline.stats
        # client states: params version picked up, pickup round, pickup time
        client_params: Dict[int, object] = {}
        pickup_round: Dict[int, int] = {}
        epochs_of: Dict[int, int] = {}
        idle_of: Dict[int, float] = {}      # gap between train-end and return
        # uplink seconds of a pickup whose contact the event clock has not
        # passed yet — the initial seed pickups and any pickup deferred
        # past a recharge stand-down. Billed at the client's next
        # processed return, by which time the clock has passed the
        # pickup's contact, so every episode's bill is uplink + training
        # + downlink, each at (or after) the contact where it happened.
        deferred_up: Dict[int, float] = {}
        # fault bookkeeping: pickup contact time of each pending episode
        # (radiation resets in (pickup, return] wipe it) and the drop walk
        # resolved at scheduling time (drops, re-billed bytes)
        pickup_t: Dict[int, float] = {}
        meta_of: Dict[int, tuple] = {}
        # seed the fleet with one batched contact-plan pass: drained
        # satellites query from their (batched) battery-recovery time
        # instead of t0 — satellites that never recover get an inf query,
        # which next_contacts reports as invalid.
        with obs.span("fl.select"):
            tq = np.full(K, t0)
            rex_seed = 0        # retry-budget exhaustions during seeding
            def_seed = 0        # policy eclipse-deferrals during seeding
            if self.energy is not None:
                self.energy.advance_to(t0)
                if self.policy.defers_in_eclipse:
                    # the policy's sunlit-arc deferral replaces the binary
                    # floor at seeding: a satellite in eclipse below the
                    # defer threshold schedules its first pickup from its
                    # sunrise (solar income) instead of the floor-recovery
                    # walk; one held dark forever sits the run out
                    soc = self.energy.soc_frac()
                    defer = ~self.energy.sunlit_at(t0) \
                        & (soc < self.policy.defer_soc)
                    if defer.any():
                        sr = self.energy.sunrise_after(t0)
                        tq[defer] = np.where(np.isfinite(sr[defer]),
                                             np.maximum(sr[defer], t0), np.inf)
                        def_seed = int(defer.sum())
                else:
                    drained = np.nonzero(~self.energy.eligible())[0]
                    if len(drained):
                        rts = self.energy.recover_times(drained)
                        tq[drained] = np.where(np.isfinite(rts),
                                               np.maximum(rts, t0), np.inf)
            if self.faults is None:
                avail, _, _, valid = plan.next_contacts(tq)
                recv_end_k = avail + self._t_up_k
                ret_avail, _, _, ret_valid = plan.next_contacts(
                    np.where(valid, recv_end_k + ep_s, np.inf))
                for k in range(K):
                    if not (valid[k] and ret_valid[k]):
                        continue
                    recv_end, ret0 = float(recv_end_k[k]), float(ret_avail[k])
                    ep = int(np.clip((ret0 - recv_end) // ep_s[k], 1,
                                     cfg.max_local_epochs))
                    queue.push(ret0 + float(self._t_down_k[k]),
                               CLIENT_RETURN, key=k)
                    client_params[k] = self._tx_global()
                    pickup_round[k] = 0
                    epochs_of[k] = ep
                    idle_of[k] = max(ret0 - (recv_end + ep * float(ep_s[k])),
                                     0.0)
                    if self.energy is not None:     # the seed pickup's uplink
                        deferred_up[k] = float(self._t_up_k[k])
            else:
                # fault-aware seed: outage-delayed pickups, outage-skipping
                # return windows, and the drop walk resolved at scheduling
                # time (the trained content never depends on the return time,
                # so resolving drops early is equivalent; staleness accrues
                # naturally from the later event time).
                tq = self.faults.next_up(np.arange(K), tq)
                for k in range(K):
                    w = self._next_available_contact(k, float(tq[k]))
                    if w is None:
                        continue
                    recv_end = float(w[0]) + float(self._t_up_k[k])
                    nxt = self._next_available_contact(
                        k, recv_end + float(ep_s[k]))
                    if nxt is None:
                        continue
                    ep = int(np.clip((nxt[0] - recv_end) // ep_s[k], 1,
                                     cfg.max_local_epochs))
                    t_done, d, rb, lost = self._walk_drops(k, nxt)
                    if lost:            # every return window drops: sits out
                        rex_seed += int(lost == _LOST_RETRIES)
                        continue
                    queue.push(t_done, CLIENT_RETURN, key=k)
                    client_params[k] = self._tx_global()
                    pickup_round[k] = 0
                    epochs_of[k] = ep
                    idle_of[k] = max(nxt[0] - (recv_end + ep * float(ep_s[k])),
                                     0.0)
                    pickup_t[k] = float(w[0])
                    meta_of[k] = (d, rb)
                    if self.energy is not None:
                        deferred_up[k] = float(self._t_up_k[k])

        buf, r = [], 0
        t_round_start = t0
        idle_acc, comm_acc, train_acc, n_ev = 0.0, 0.0, 0.0, 0
        energy_acc, skip_acc = 0.0, 0
        fault_acc, drop_acc, rebill_acc = 0, 0, 0.0
        rex_acc, def_acc = rex_seed, def_seed
        comm_by: Dict[int, float] = {}
        while queue and r < max_rounds:
            ev = queue.pop()
            t_ret, k = ev.t, ev.key
            if t_ret > t_end:
                break
            with obs.span("fl.return"):
                timeline.advance_through(t_ret)
                st.add(CLIENT_RETURN)
                t_up = float(self._t_up_k[k])
                t_down = float(self._t_down_k[k])
                train_s = epochs_of[k] * float(ep_s[k])
                # a radiation reset since pickup wiped the client's local
                # state: the episode's update (and any in-flight downlink)
                # is lost. Nothing is billed — the reset, not the radio, lost
                # it — and the client re-syncs by picking up the current
                # global at this same contact.
                wiped = (self.faults is not None
                         and self.faults.cfg.has_resets
                         and self.faults.reset_in(k, pickup_t.get(k, t0),
                                                  t_ret))
                n_drops = 0
                if not wiped:
                    # queued, not trained: the flush trains the buffer
                    stale = r - pickup_round[k]
                    wgt = (1.0 + stale) ** (-cfg.staleness_exponent)
                    buf.append((k, client_params[k], epochs_of[k], wgt,
                                t_ret))
                    comm_acc += t_up + t_down
                    comm_by[k] = comm_by.get(k, 0.0) + t_up + t_down
                    train_acc += train_s
                    idle_acc += idle_of.get(k, 0.0)
                    n_ev += 1
                    st.add(TRAIN_DONE)
                    if self.faults is not None:
                        # the drop walk resolved at scheduling time: retry
                        # airtime joins the episode's comm accounting
                        n_drops, rb = meta_of.get(k, (0, 0.0))
                        drop_acc += n_drops
                        rebill_acc += rb
                        comm_acc += n_drops * t_down
                        comm_by[k] = comm_by.get(k, 0.0) \
                            + n_drops * t_down
                else:
                    fault_acc += 1
                    deferred_up.pop(k, None)
                # client immediately picks up the current global and continues
                with obs.span("fl.select"):
                    recv_end = t_ret + t_up
                    requeue, stood_down = True, False
                    if self.energy is not None:
                        self.energy.advance_to(t_ret)
                        # the completed episode is billed at its return
                        # contact: training, the downlink(s) that just
                        # happened — retries included — and any pickup
                        # uplink deferred past a stand-down (whose contact
                        # the clock has now passed)
                        if not wiped:
                            energy_acc += self.energy.bill_activity(
                                np.array([k]), np.array([train_s]),
                                np.array([t_down * (1 + n_drops)
                                          + deferred_up.pop(k, 0.0)]))
                        elig = self.energy.eligible()
                        timeline.note_eligibility(elig, t_ret)
                        if self.policy.defers_in_eclipse:
                            # the policy's sunlit-arc deferral replaces
                            # the binary floor stand-down: in eclipse below
                            # the defer threshold, the next pickup waits for
                            # this satellite's sunrise (when solar income
                            # resumes) instead of walking to the SoC-floor
                            # recovery
                            if float(self.energy.soc_frac()[k]) \
                                    < self.policy.defer_soc and not bool(
                                        self.energy.sunlit_at(t_ret)[k]):
                                def_acc += 1
                                stood_down = True
                                sr = float(
                                    self.energy.sunrise_after(t_ret)[k])
                                w2 = self._next_available_contact(
                                    k, max(sr, recv_end)) \
                                    if np.isfinite(sr) else None
                                if w2 is None:
                                    requeue = False  # dark forever: out
                                else:
                                    recv_end = w2[0] + t_up
                        elif not elig[k]:
                            # drained below the floor: stand down until
                            # idle+solar recovers, then rejoin at the next
                            # contact after that. The deferred pickup's
                            # uplink is billed where it actually happens
                            # (post-recovery), not here — at this point the
                            # battery could not pay it and the charge would
                            # vanish into the SoC floor clamp.
                            skip_acc += 1
                            stood_down = True
                            w2 = self._post_recovery_contact(k, recv_end)
                            if w2 is None:
                                requeue = False  # never recovers: out
                            else:
                                recv_end = w2[0] + t_up
                    nxt = self._next_available_contact(
                        k, recv_end + float(ep_s[k])) if requeue else None
                    ev_t, d2, rb2 = None, 0, 0.0
                    if nxt is not None:
                        ev_t = float(nxt[0]) + t_down
                        if self.faults is not None:
                            t_done2, d2, rb2, lost = self._walk_drops(k, nxt)
                            if lost:    # every remaining return window drops
                                rex_acc += int(lost == _LOST_RETRIES)
                                nxt = None
                            else:
                                ev_t = t_done2
                    if nxt is not None:
                        # the next pickup really starts an episode: bill its
                        # uplink — now, if it happens at this same contact;
                        # via deferred_up at the post-recovery contact
                        # otherwise. A client with no remaining return
                        # contact performs no pickup, so (symmetrically in
                        # both paths) none is billed.
                        if self.energy is not None:
                            if stood_down:
                                deferred_up[k] = t_up
                            else:
                                energy_acc += self.energy.bill_activity(
                                    np.array([k]), np.array([0.0]),
                                    np.array([t_up]))
                        ep = int(np.clip((nxt[0] - recv_end) // ep_s[k], 1,
                                         cfg.max_local_epochs))
                        queue.push(ev_t, CLIENT_RETURN, key=k)
                        client_params[k] = self._tx_global()
                        pickup_round[k] = r
                        epochs_of[k] = ep
                        idle_of[k] = max(
                            nxt[0] - (recv_end + ep * float(ep_s[k])), 0.0)
                        if self.faults is not None:
                            pickup_t[k] = recv_end - t_up
                            meta_of[k] = (d2, rb2)
                    elif self.energy is not None or self.faults is not None:
                        # the client drops out of the pending set for good
                        # (no recovery contact, or no usable window left):
                        # purge its per-client state so nothing dangles — in
                        # particular epochs_of, whose stale entry would skew
                        # every later round's epoch average. No bytes are
                        # billed for a pickup that never happens. (Gated so
                        # the fault-free/energy-free path stays
                        # byte-identical to round_engine_ref.)
                        for dct in (client_params, pickup_round, epochs_of,
                                    idle_of, deferred_up, pickup_t, meta_of):
                            dct.pop(k, None)

                if len(buf) >= cfg.buffer_size:
                    st.add(ROUND_BARRIER)
                    self._flush_buffer(buf)
                    buf = []
                    acc = self.evaluate() if r % cfg.eval_every == 0 else \
                        (self.records[-1].accuracy if self.records else 0.0)
                    dur = t_ret - t_round_start
                    self.records.append(RoundRecord(
                        r, t_round_start, t_ret, dur,
                        idle_acc / max(n_ev, 1),
                        comm_acc / max(n_ev, 1), train_acc / max(n_ev, 1),
                        acc, [],
                        epochs=float(np.mean(list(epochs_of.values())))
                        if epochs_of else 0.0,
                        energy_wh=energy_acc, skipped_low_power=skip_acc,
                        comm_s_by_sat=comm_by, skipped_faulted=fault_acc,
                        dropped_contacts=drop_acc, retransmit_bytes=rebill_acc,
                        corrupted_updates=self._last_flush_corrupted,
                        clipped_updates=self._last_flush_clipped,
                        retries_exhausted=rex_acc,
                        storm_events=self._storms_in(t_round_start, t_ret),
                        policy_deferred=def_acc,
                        policy_skips={"eclipse_deferred": def_acc}
                        if def_acc else {}))
                    t_round_start = t_ret
                    idle_acc = comm_acc = train_acc = 0.0
                    energy_acc, skip_acc = 0.0, 0
                    fault_acc, drop_acc, rebill_acc = 0, 0, 0.0
                    rex_acc, def_acc = 0, 0
                    comm_by = {}
                    n_ev = 0
                    r += 1
                    self.trace.round_done()
        return self.records


ALGORITHMS = {
    "fedavg": (FedAvgSat, {}),
    "fedavg_sch": (FedAvgSat, {"selection": "scheduled"}),
    "fedavg_intrasl": (FedAvgSat, {"selection": "intra_sl"}),
    "fedprox": (FedProxSat, {}),
    "fedprox_sch": (FedProxSat, {"selection": "scheduled"}),
    "fedprox_schv2": (FedProxSat, {"selection": "scheduled", "min_epochs": 2}),
    "fedprox_intrasl": (FedProxSat, {"selection": "intra_sl"}),
    "fedbuff": (FedBuffSat, {}),
}
