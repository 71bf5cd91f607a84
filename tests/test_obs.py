"""The span-and-counter recorder (``repro.obs``) and the spans the FL
engines open, on the CPU at a tiny size."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.autoflsat import AutoFLSat
from repro.core.contact_plan import build_contact_plan
from repro.core.spaceify import ALGORITHMS, FedAvgSat, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.sim.events import EventStats
from repro.sim.flystack import FLySTacK, SimConfig
from repro.sim.hardware import FLYCUBE

# the spans every engine opens on its round path, one per layer
LAYER_SPANS = ("fl.run", "fl.select", "fl.train", "fl.aggregate",
               "fl.evaluate", "fl.sync", "world.advance")


@pytest.fixture(scope="module")
def world():
    plan = build_contact_plan(2, 5, 3, horizon_s=86_400.0, dt_s=60.0)
    ds = make_federated_dataset("femnist", n_clients=10, n_per_client=16,
                                alpha=0.5, seed=0)
    return plan, ds


def tiny_cfg(seed=3, **kw):
    base = dict(model="mlp", clients_per_round=4, epochs=1, batch_size=8,
                max_rounds=3, seed=seed)
    return FLConfig(**{**base, **kw})


class FakeClock:
    """``obs._now`` stand-in: each call returns the next of ``times``."""

    def __init__(self, *times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


def test_nested_spans_split_total_and_self_time(monkeypatch):
    monkeypatch.setattr(obs, "_now", FakeClock(0, 10, 40, 50, 70, 100))
    trace = obs.RunTrace(1, "test")
    with obs.recording(trace):
        with obs.span("outer") as outer:
            with obs.span("inner"):
                pass
            with obs.span("inner"):
                pass
    assert outer.ns == 100
    assert trace.spans == {"outer": [1, 100, 50], "inner": [2, 50, 50]}


def test_spans_outside_a_run_record_nothing():
    trace = obs.RunTrace(1, "test")
    with obs.span("alone"):
        obs.count("c")
    with obs.recording(trace):
        pass
    assert trace.spans == {} and trace.counters == {}
    assert obs._open == [] and obs._active == []


def test_counters_add_into_the_innermost_run():
    outer, inner = obs.RunTrace(1, "a"), obs.RunTrace(2, "b")
    with obs.recording(outer):
        obs.count("n")
        with obs.recording(inner):
            obs.count("n", 2.5)
            obs.count("m")
        obs.count("n")
    assert outer.counters == {"n": 2}
    assert inner.counters == {"n": 2.5, "m": 1}


def test_sync_reads_the_host_value_in_its_own_span():
    trace = obs.RunTrace(1, "test")
    x = jnp.arange(4) * 2
    with obs.recording(trace):
        got = obs.sync(x)
        assert int(obs.sync(x.sum())) == 12
    assert isinstance(got, np.ndarray) and got.tolist() == [0, 2, 4, 6]
    assert trace.counters["host_syncs"] == 2
    assert trace.spans["fl.sync"][0] == 2


def test_compilations_are_keyed_by_the_innermost_span():
    trace = obs.RunTrace(1, "test")

    @jax.jit
    def fresh(x):                   # a program no other test compiles
        return jnp.cos(x) * 3.0 + 0.125

    with obs.recording(trace), obs.span("outer"), obs.span("step"):
        fresh(jnp.ones(7)).block_until_ready()
    c = trace.counters
    assert c["compiles"] >= 1 and c["compiles.step"] == c["compiles"]
    assert 0 < c["compile_s.step"] <= c["compile_s"]
    assert "compiles.outer" not in c


def test_the_log_keeps_the_last_runs():
    class Engine:
        def __init__(self, seed):
            self.trace = obs.RunTrace(seed, "dummy")

        @obs.traced_run
        def run(self):
            obs.count("ran")
            self.trace.round_done()
            return self.trace.seed

    seeds = [("bound", i) for i in range(obs.LOG_SIZE + 5)]
    for s in seeds:
        assert Engine(s).run() == s
    assert len(obs._log) == obs.LOG_SIZE
    assert obs.runs(seeds[:5]) is None             # pushed out
    last = obs.runs(seeds[-3:])
    assert last.rounds == 3 and last.counters == {"ran": 3}
    assert last.spans["fl.run"][0] == 3


def test_a_run_that_raises_is_logged():
    class Engine:
        trace = obs.RunTrace(("raises", 1), "dummy")

        @obs.traced_run
        def run(self):
            raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        Engine().run()
    assert obs.runs([("raises", 1)]).spans["fl.run"][0] == 1
    assert obs._open == [] and obs._active == []


def test_one_trace_per_engine_run_keyed_by_seed(world):
    plan, ds = world
    a = FedAvgSat(plan, FLYCUBE, ds, tiny_cfg(seed=101))
    b = FedAvgSat(plan, FLYCUBE, ds, tiny_cfg(seed=102))
    ra, rb = a.run(), b.run()
    assert a.trace is not b.trace and a.trace.seed == 101
    assert obs.runs([101]).rounds == len(ra) == a.trace.rounds
    both = obs.runs([101, 102])
    assert both.rounds == len(ra) + len(rb)
    assert both.counters["host_syncs"] == \
        a.trace.counters["host_syncs"] + b.trace.counters["host_syncs"]
    assert both.events.counts["round_barrier"] == len(ra) + len(rb)
    assert obs.runs([101, 424242]) is None        # a seed no engine ran
    # construction is recorded too, under its own span
    assert a.trace.spans["fl.init"][0] == 1
    # a seed run again reads its newest run
    again = FedAvgSat(plan, FLYCUBE, ds, tiny_cfg(seed=101, max_rounds=1))
    again.run()
    assert obs.runs([101]).round_ns == again.trace.round_ns
    assert obs.runs([101]).rounds == 1


@pytest.mark.parametrize("eval_every", [1, 2])
def test_host_syncs_count_the_reads_of_a_fedavg_round(world, eval_every):
    """Each FedAvg round reads the cohort's keys back once, and each
    evaluated round reads the correct count once: nothing else."""
    plan, ds = world
    algo = FedAvgSat(plan, FLYCUBE, ds,
                     tiny_cfg(seed=200 + eval_every, eval_every=eval_every))
    recs = algo.run()
    evaluated = sum(r.round % eval_every == 0 for r in recs)
    assert len(recs) == 3
    assert algo.trace.counters["host_syncs"] == len(recs) + evaluated
    assert algo.trace.spans["fl.sync"][0] == len(recs) + evaluated
    assert algo.trace.spans["fl.evaluate"][0] == evaluated
    assert algo.trace.counters["cohort_pad_slots"] == \
        sum(4 - len(r.participants) for r in recs)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "fedbuff",
                                       "autoflsat"])
def test_every_engine_opens_its_layer_spans(world, algorithm):
    plan, ds = world
    if algorithm == "autoflsat":
        plan = build_contact_plan(2, 5, 3, horizon_s=86_400.0, dt_s=60.0,
                                  with_isl_pairs=True)
        algo = AutoFLSat(plan, FLYCUBE, ds, tiny_cfg(seed=301),
                         epochs_mode="auto")
    else:
        cls, over = ALGORITHMS[algorithm]
        algo = cls(plan, FLYCUBE, ds, tiny_cfg(seed=302, **over))
    recs = algo.run()
    t = algo.trace
    assert t.rounds == len(recs) > 0 and t.algorithm == algorithm
    for name in LAYER_SPANS:
        assert t.per_round_ms(name) >= 0.0, name
        count, total, self_ns = t.spans[name]
        assert count > 0 and 0 <= self_ns <= total
    assert ("fl.return" if algorithm == "fedbuff" else "fl.round") in t.spans
    # the round wall times cover the spans opened inside them
    assert sum(t.round_ns) <= t.spans["fl.run"][1]
    assert t.spans["fl.run"][1] == sum(v[2] for k, v in t.spans.items()
                                       if k != "fl.init")
    assert algo.event_stats is t.events
    s = t.summary()
    assert s["rounds"] == len(recs) and s["round_p95_ms"] > 0
    assert list(s["self_ms_per_round"].values()) == \
        sorted(s["self_ms_per_round"].values(), reverse=True)


def test_sim_result_carries_the_run_trace():
    cfg = SimConfig(algorithm="fedavg", n_clusters=2, sats_per_cluster=5,
                    n_per_client=16, horizon_days=1.0, dt_s=60.0,
                    fl=tiny_cfg(seed=401))
    res = FLySTacK(cfg).run()
    assert res.trace.seed == 401 and res.trace.rounds == len(res.records)
    ev = res.trace.events
    assert isinstance(ev, EventStats)
    assert ev.counts["round_barrier"] == len(res.records)
    assert ev.counts["train_done"] == sum(len(r.participants)
                                          for r in res.records)


def test_a_span_shows_on_the_profilers_host_plane(tmp_path):
    """In a profiled run the span is a host event under its bare name, on
    the trace's clock, and lasts what the recorder measured."""
    import time
    trace = obs.RunTrace(1, "test")
    with jax.profiler.trace(str(tmp_path)):
        with obs.recording(trace), obs.span("obs.profiled") as sp:
            time.sleep(0.02)
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    plane = pd.find_plane_with_name("/host:CPU")
    found = [e for line in plane.lines for e in line.events
             if e.name == "obs.profiled"]
    assert len(found) == 1
    assert abs(found[0].duration_ns - sp.ns) < 1e6
    assert trace.spans["obs.profiled"][1] == sp.ns >= 2e7
