"""The benchmark harness: one run of one cell, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
harness reads ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``
(which names its driver, ``bench/drivers/<driver>.py``), the cell's limits
``bench/limits/<cell>.json`` and, in a traced run, one reader
``bench/metrics/<metric>.py`` per per-layer metric. The driver runs the
window and names its own end-to-end values (``Sim.end_to_end``). A new
cell, mix, driver or metric is a new file; no file here changes for it.
"""
from __future__ import annotations

import glob
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import types

import jax

from bench import compare, counts, trace

WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class MissingMetric(RuntimeError):
    """A per-layer metric listed for the cell found nothing to read."""


def log(*msg) -> None:
    print(*msg, file=sys.stderr, flush=True)


def load(root, workload: str) -> dict:
    """The cell ``workload`` with its parsed files, from the checkout at
    ``root``."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    read = lambda *p: json.loads(root.joinpath("bench", *p).read_text())
    applies = lambda m: workload in m.get("workloads", [workload])
    return {"root": root, "cell": cell,
            "config": read("configs", cell["config"] + ".json"),
            "traffic": read("traffic", cell["traffic"] + ".json"),
            "limits": read("limits", workload + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def metric_reader(root, name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int):
    """The cell's devices; raises ``NoChip`` rather than fall back."""
    found = jax.devices()
    if found[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are on {found[0].platform!r}")
    if len(found) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(found)}")
    return found[:chips]


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.n += 1


def run(spec: dict, seed: int, seconds: float, traced: bool, t_start: float,
        devs=None) -> dict:
    """One run; returns the result object the command prints. ``t_start``
    is the process's start on the host clock; ``devs`` the devices the
    result names (the chips the cell asked for)."""
    traffic = spec["traffic"]
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    sim = driver.Sim(spec["config"], traffic, seed, log)
    compiles = CompileCounter()
    sim.setup()
    setup_s = time.perf_counter() - t_start

    tmp = None
    if traced:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    n0 = compiles.n
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        attempted, failed, elapsed = sim.window(seconds)
    in_window = compiles.n - n0
    if traced:
        jax.profiler.stop_trace()
    log(f"compilations in the window: {in_window}")
    device = {"platform": devs[0].platform if devs else "none",
              "kind": devs[0].device_kind if devs else "none",
              "count": len(devs) if devs else 0,
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs) if devs else 0}

    numbers = sim.check()
    limits = spec["limits"]
    ok = compare.verdict(numbers, limits)

    metrics, breakdown = {}, None
    if traced:
        summary = reduce_trace(tmp)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = types.SimpleNamespace(
            summary=summary, sim=sim, window_s=elapsed,
            peak=counts.peaks(device["kind"]) if devs else None)
        for m in spec["per_layer"]:
            value = metric_reader(spec["root"], m["name"])(ctx)
            if value is None:
                raise MissingMetric(
                    f"{m['name']} found nothing to read in {spec['cell']['name']}"
                    ", which BENCHMARK.json lists for it")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, **sim.end_to_end(elapsed)}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    result = {"correct": bool(ok) and failed == 0 and attempted > 0,
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def reduce_trace(tmp: str):
    try:
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        pd = jax.profiler.ProfileData.from_file(path)
        lo, hi = trace.window_of(pd, WINDOW_SPAN)
        return trace.summarize(pd, lo, hi)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
