"""The control of the LM cell's comparison: the reference, one precision
step below the cell's bfloat16, put in the program's place.

  python bench/control_hfl.py --workload <name> --seeds <n> [<n> ...]

For each seed it draws the cell's first round, trains it with the
reference whose matmul operands (and their cotangents) are rounded to
``BITS`` mantissa bits (float8_e4m3's precision, float32's range), and
holds the result against the float32 reference with the cell's
comparison and limits: each step's loss against the float32 loss of the
weights that step starts from, the round's change of the weights against
the float32 round's; the replay and replica numbers are the control's
own (0: one function, one mean). A sound comparison reads the control as
not correct on every seed. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Mantissa bits of the control's matmul operands (bfloat16 has 7).
BITS = 3


def control_numbers(spec: dict, seed: int, log) -> dict:
    """The compared numbers of the control of the cell ``spec``."""
    import jax

    from bench.drivers import hfl
    from bench.reference import mamba2 as ref
    sim = hfl.Sim(spec["config"], spec["traffic"], seed, log)
    sim.tokens = sim.draw_tokens()
    batches = sim.reference_batches(0)
    run = lambda bits: ref.train_round(sim.weight_seed, sim.dims, sim.hp,
                                       batches, jax.devices(), bits)
    losses, at_weights, w = run(BITS)
    params = jax.device_get(w)
    del w
    _, _, ref_w = run(None)
    return {"replay_loss_diff": 0.0, "replica_gap": 0.0,
            **hfl.compare(losses, at_weights, params, ref_w,
                          sim.weight_seed, sim.dims)}


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
