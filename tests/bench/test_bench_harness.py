"""The harness end to end on the CPU at a tiny size: finding new files by
name, refusing to run without a TPU, and reading ``correct`` as false
when the program is broken underneath or replaced by the control."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import compare, control, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ["w10x10.fedavg-q8", "w10x10.autoflsat", "w10x10.fedbuff"]


def run(spec, seed=2 ** 33 + 7, seconds=0.2):
    return harness.run(spec, seed, seconds, False, time.perf_counter(), None)


def test_new_files_are_found_by_name(tmp_path, tiny_spec, benchmark_json):
    """A cell, configuration, traffic mix, limits and per-layer metric that
    exist only as new files in another checkout are found by name."""
    before = (ROOT / "BENCHMARK.json").read_bytes()
    spec = tiny_spec("w10x10.fedavg-q8")
    bench = dict(benchmark_json)
    bench["workloads"] = bench["workloads"] + [
        {"name": "tiny.new", "config": "tiny-world", "traffic": "tiny-mix",
         "chips": 1, "why": "a cell that only new files define"}]
    bench["per_layer"] = bench["per_layer"] + [
        {"name": "new_metric", "unit": "s", "better": "lower",
         "source": "host_clock", "layer": "world build (core/contact_plan)",
         "moves": "setup_s", "workloads": ["tiny.new"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub, name, obj in [("configs", "tiny-world.json", spec["config"]),
                           ("traffic", "tiny-mix.json", spec["traffic"]),
                           ("limits", "tiny.new.json", spec["limits"])]:
        (tmp_path / "bench" / sub).mkdir(parents=True)
        (tmp_path / "bench" / sub / name).write_text(json.dumps(obj))
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.sim.world_build_s\n")

    new = harness.load(tmp_path, "tiny.new")
    assert new["config"] == spec["config"]
    assert [m["name"] for m in new["per_layer"]] == ["new_metric"]
    result = run(new)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    reader = harness.metric_reader(tmp_path, "new_metric")

    class Ctx:
        class sim:
            world_build_s = 1.5
    assert reader(Ctx) == 3.0
    assert (ROOT / "BENCHMARK.json").read_bytes() == before


def test_listed_metric_that_reads_nothing_is_an_error(monkeypatch, tiny_spec):
    """A per-layer metric that BENCHMARK.json lists for a cell may not drop
    out of its traced result: here the kernel's roofline, in a cell whose
    traffic never runs the kernel."""
    from bench import trace
    spec = tiny_spec(CELLS[1])
    spec["per_layer"] = [{"name": "quant_agg_roofline.sim", "unit": "%"}]
    summary = trace.Summary(devices=1, window_s=1.0, busy_s=0.5, launches=4,
                            program_s={}, program_launches={}, op_s={},
                            gaps=[])
    monkeypatch.setattr(harness.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(harness.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(harness, "reduce_trace",
                        lambda tmp: shutil.rmtree(tmp) or summary)
    with pytest.raises(harness.MissingMetric, match="quant_agg_roofline"):
        harness.run(spec, 12345, 0.2, True, time.perf_counter(), None)


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def _unchanged(self, *args):
    return self.global_params, 0


def _flush_nothing(self, buf):
    self._last_flush_clipped = 0


def _half_left_out(original):
    def aggregate(self, stacked, weights):
        w = __import__("numpy").asarray(weights, float).copy()
        live = w.nonzero()[0]
        w[live[len(live) // 2:]] = 0.0
        return original(self, stacked, w)
    return aggregate


def _half_flushed(original):
    def flush(self, buf):
        return original(self, buf[:(len(buf) + 1) // 2])
    return flush


def _accuracy_altered(original):
    calls = []

    def evaluate(self):
        calls.append(1)
        return original(self) + 0.5 * (len(calls) % 2)
    return evaluate


def _participant_altered(original):
    def run_round(self, r, t):
        rec = original(self, r, t)
        if rec is not None and r == 1:
            rec.participants = [rec.participants[0] + 1] \
                + list(rec.participants[1:])
        return rec
    return run_round


FAULTS = [(c, f) for c in CELLS[:2] + ["fedbuff"] for f in
          ("state_unchanged", "half_left_out", "accuracy_altered")] \
    + [(c, "participant_altered") for c in CELLS[:2]]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_broken_program_reads_not_correct(monkeypatch, tiny_spec, cell,
                                          fault):
    """Each fault the cell can have, planted in the program: the server
    step returns the global model unchanged; half of the cohort (or of
    FedBuff's buffer) is left out and the mean taken over the rest; an
    accuracy or a participant altered where it is produced."""
    from repro.core.autoflsat import AutoFLSat
    from repro.core.spaceify import FedAvgSat, FedBuffSat, SpaceifiedFL
    buffered = cell == "fedbuff"
    spec = tiny_spec(CELLS[2] if buffered else cell)
    if fault == "state_unchanged":
        assert run(spec)["correct"]
    if fault == "state_unchanged":
        monkeypatch.setattr(*((FedBuffSat, "_flush_buffer", _flush_nothing)
                              if buffered else
                              (SpaceifiedFL, "_aggregate", _unchanged)))
    elif fault == "half_left_out":
        if buffered:
            monkeypatch.setattr(FedBuffSat, "_flush_buffer",
                                _half_flushed(FedBuffSat._flush_buffer))
        else:
            monkeypatch.setattr(SpaceifiedFL, "_aggregate",
                                _half_left_out(SpaceifiedFL._aggregate))
    elif fault == "accuracy_altered":
        monkeypatch.setattr(SpaceifiedFL, "evaluate",
                            _accuracy_altered(SpaceifiedFL.evaluate))
    else:
        engine = AutoFLSat if cell == CELLS[1] else FedAvgSat
        monkeypatch.setattr(engine, "run_round",
                            _participant_altered(engine.run_round))
    result = run(spec)
    assert result["attempted"] > 0
    assert not result["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(tiny_spec, cell):
    spec = tiny_spec(cell)
    numbers = control.control_numbers(spec, 2 ** 32 + 11, harness.log)
    assert not compare.verdict(numbers, spec["limits"])
