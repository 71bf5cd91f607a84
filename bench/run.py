"""Run one benchmark cell on the chip and print its result.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout. The cell, its configuration and its
traffic mix are found by name through ``BENCHMARK.json``. With ``--trace
0`` the result carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window. The
last line of standard output is the result as one JSON object; the
compared numbers and their limits are the last lines of standard error.
Without a TPU (or with fewer chips than the cell asks for) it exits
nonzero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout (the path is part of each entry's key); the program keeps
    # its cache wherever this variable points.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    spec = harness.load(ROOT, args.workload)
    try:
        devs = harness.devices(spec["cell"]["chips"])
    except harness.NoChip as e:
        harness.log(f"error: {e}")
        return 2
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         T_START, devs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
