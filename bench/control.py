"""The control of a simulator cell's comparison: the reference, one
precision lower, put in the program's place.

  python bench/control.py --workload <name> --seeds <n> [<n> ...]

For each seed it makes the cell's data, takes the schedule from the
reference world computed in bfloat16 and the jobs' models and accuracies
from the reference replayed in bfloat16, and holds them against the
float32 reference with the cell's comparison and limits. A sound
comparison reads the control as not correct on every seed. The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_numbers(spec: dict, seed: int, log) -> dict:
    """The compared numbers of one control job of the cell ``spec``."""
    import importlib

    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from bench import compare
    driver = importlib.import_module(
        f"bench.drivers.{spec['traffic']['driver']}")
    sim = driver.Sim(spec["config"], spec["traffic"], seed, log)
    sim.make_data()
    job_seed = sim.job_seed(0)
    ref = sim.reference()
    low, _ = sim.reference(ml_dtypes.bfloat16)
    sched = ref[0]
    job = compare.Job(job_seed, [r + (0.0,) for r in sched])
    p, acc, p0 = sim.replay(job, ref)
    q, acc_q, _ = sim.replay(job, ref, dtype=jnp.bfloat16)
    f32 = lambda t: {k: np.asarray(v, np.float32) for k, v in t.items()}
    job = compare.Job(job_seed, [r[:4] + (a,) for r, a in
                                 zip(low, acc_q + [0.0] * len(low))],
                      f32(q))
    numbers = compare.schedule_numbers([job], sched)
    job.rounds = job.rounds[:len(acc)]
    numbers.update(compare.model_numbers(job, f32(p), f32(p0), acc))
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import compare, harness
    spec = harness.load(ROOT, args.workload)
    try:
        devs = harness.devices(spec["cell"]["chips"])
    except harness.NoChip as e:
        harness.log(f"error: {e}")
        return 2
    for seed in args.seeds:
        t0 = time.perf_counter()
        numbers = control_numbers(spec, seed, harness.log)
        print(json.dumps({"seed": seed, "device": devs[0].device_kind,
                          "correct": compare.verdict(numbers, spec["limits"]),
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
