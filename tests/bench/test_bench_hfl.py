"""The LM cell's driver on the CPU at a tiny size: the window and ``check()``
on four devices as the cell's (pod=2, data=1, model=2) mesh, the faults a
broken trainer would plant reading not correct, and what the trainer's
recorder leaves behind the window."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness
from bench.drivers import hfl
from repro import obs

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "mamba2-1.3b.hfl2x2-4chip"
SEED = 2 ** 34 + 11
HFL_SPANS = ("hfl.round", "hfl.place_batch", "hfl.local", "hfl.sync",
             "fl.sync")


def tiny(spec: dict, h_sync: int = 8) -> dict:
    """Two layers of width 64 over a vocabulary of 256, 32 tokens a step;
    every other number as the cell has it."""
    spec["config"]["model"].update(n_layers=2, d_model=64, vocab=256,
                                   d_state=16, head_dim=16, chunk=16,
                                   compute_dtype="float32")
    spec["traffic"].update(seq_len=32, h_sync=h_sync, pool_rounds=2)
    return spec


def test_window_and_check_on_four_devices(tmp_path):
    """The harness runs the cell on four host devices, the cell's mesh,
    with the reference's pods on two devices each, and reads correct."""
    script = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        import jax
        from bench import harness
        sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})
        from test_bench_hfl import tiny
        spec = tiny(harness.load({str(ROOT)!r}, {CELL!r}))
        res = harness.run(spec, {SEED}, 0.0, False, time.perf_counter())
        print(json.dumps({{"devices": jax.device_count(), **res}}))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}
    assert "compilations in the window: 0" in out.stderr


def frozen_local(programs):
    """The local step trains a copy and leaves the state unchanged."""
    def plant(*args, **kwargs):
        init, local, place_batch = programs(*args, **kwargs)

        def frozen(state, batch):
            _, m = local(jax.tree.map(jnp.copy, state), batch)
            return state, m
        return init, frozen, place_batch
    return plant


def half_batch(programs):
    """The last pod's labels lose their second half (masked out)."""
    def plant(*args, **kwargs):
        init, local, place_batch = programs(*args, **kwargs)

        def place(batches):
            last = dict(batches[-1])
            labels = np.array(last["labels"])
            labels[..., labels.shape[-1] // 2:] = -1
            last["labels"] = labels
            return place_batch(list(batches[:-1]) + [last])
        return init, local, place
    return plant


def no_exchange(sync):
    """The tier-2 exchange is left out: the pods keep their own state."""
    return lambda *args, **kwargs: (lambda state: state)


@pytest.mark.parametrize("name,fault", [
    ("hfl_programs", frozen_local), ("hfl_programs", half_batch),
    ("hfl_sync", no_exchange)])
def test_planted_fault_reads_not_correct(monkeypatch, name, fault):
    monkeypatch.setattr(hfl, name, fault(getattr(hfl, name)))
    spec = tiny(harness.load(ROOT, CELL), h_sync=4)
    sim = hfl.Sim(spec["config"], spec["traffic"], SEED, print)
    sim.setup()
    sim.window(0.0)
    numbers = sim.check()
    assert not compare.verdict(numbers, spec["limits"]), numbers


def test_window_leaves_one_run_a_round():
    """Each round of the window is one logged ``RunTrace`` of the trainer,
    with every ``hfl.*`` span, its counters and one closed round."""
    spec = tiny(harness.load(ROOT, CELL), h_sync=4)
    sim = hfl.Sim(spec["config"], spec["traffic"], SEED, print)
    sim.setup()
    attempted, failed, _ = sim.window(0.3)
    assert attempted == sim.window_rounds >= 1 and failed == 0
    run = obs.runs(sim.round_seeds)
    assert run is not None and run.algorithm == "hfl"
    assert run.rounds == sim.window_rounds == len(run.round_ns)
    for name in HFL_SPANS:
        assert run.spans[name][0] > 0 and run.per_round_ms(name) >= 0, name
    c = run.counters
    assert c["hfl_steps"] == 4 * run.rounds
    assert c["hfl_syncs"] == run.spans["hfl.sync"][0] == run.rounds
    assert c["host_syncs"] == run.rounds
    assert c["tokens"] == sim.window_tokens() == run.rounds * 4 * 2 * 32
    assert obs.runs(sim.round_seeds + [sim.warm_seed + 1]) is None


def test_readers_pick_the_trainers_programs():
    """The cell's device readers take the local-step and sync programs by
    name, per round and per device; the simulator cells' idle and launch
    readers read the cell's rounds too; ``mfu.hfl`` counts trained
    tokens."""
    from bench import counts, trace
    summary = trace.Summary(
        devices=4, window_s=10.0, busy_s=9.0, launches=40,
        program_s={"jit_train_step": 8.0, "jit_sync": 2.0,
                   "jit_sync_other": 5.0, "jit_make_state": 3.0},
        program_launches={}, op_s={}, gaps=[])
    spec = tiny(harness.load(ROOT, CELL), h_sync=4)
    sim = hfl.Sim(spec["config"], spec["traffic"], SEED, print)
    sim.window_rounds, sim.chips, sim.round_seeds = 4, 4, []
    peak = counts.peaks("TPU v5 lite")

    class Ctx:
        pass
    ctx = Ctx()
    ctx.summary, ctx.sim, ctx.window_s, ctx.peak = summary, sim, 10.0, peak
    read = lambda name: harness.metric_reader(ROOT, name)(ctx)
    assert read("local_device_ms.hfl") == 2000.0
    assert read("sync_device_ms.hfl") == 500.0
    assert read("device_idle.sim") == pytest.approx(10.0)
    assert read("dispatches_per_round.sim") == 10.0
    flops = sim.train_flops_per_token() * 4 * 4 * 2 * 32
    assert read("mfu.hfl") == pytest.approx(
        100 * flops / (10.0 * 4 * peak["bf16_flops_per_s"]))
    assert read("place_batch_ms.hfl") is None      # no logged round
