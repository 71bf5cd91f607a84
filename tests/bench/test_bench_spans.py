"""What the program's recorder (``repro.obs``) leaves behind a benchmark
window, on the CPU at a tiny size: one logged run per job, keyed by the
job's FL seed, with every layer span of the round path opened, so that
a per-layer metric can read it."""
import math

import pytest

from bench.drivers import flsim
from repro import obs

CELLS = ["w10x10.fedavg-q8", "w10x10.autoflsat", "w10x10.fedbuff"]
LAYER_SPANS = ("fl.select", "fl.train", "fl.aggregate", "fl.evaluate",
               "world.advance", "fl.sync")


def window(spec, seed):
    sim = flsim.Sim(spec["config"], spec["traffic"], seed, lambda *a: None)
    sim.setup()
    sim.window(0.0)
    return sim


@pytest.mark.parametrize("cell", CELLS)
def test_each_window_job_leaves_its_run(cell, tiny_spec):
    sim = window(tiny_spec(cell), 2 ** 35 + 91)
    seeds = [j.seed for j in sim.jobs]
    runs = obs.runs(seeds)
    assert runs is not None and runs.rounds == sim.window_rounds > 0
    for name in LAYER_SPANS:
        ms = runs.per_round_ms(name)
        assert ms is not None and math.isfinite(ms) and ms >= 0, name
    assert runs.counters["host_syncs"] >= runs.rounds
    # the warm-up job's run is logged under its own seed
    warm = obs.runs([sim.warm_seed])
    assert warm is not None and warm.seed == (sim.warm_seed,)
    # a job whose run is not in the log reads nothing
    assert obs.runs(seeds + [sim.warm_seed + 1]) is None
