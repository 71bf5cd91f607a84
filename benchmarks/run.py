"""Benchmark harness: one section per paper table/figure (DESIGN.md §6).

  PYTHONPATH=src python -m benchmarks.run            # all sections
  PYTHONPATH=src python -m benchmarks.run power quafl  # a subset
  PYTHONPATH=src python -m benchmarks.run --smoke      # CI: fail hard

Each section prints CSV rows; the roofline section reads the dry-run
artifacts (run `python -m repro.launch.dryrun` first for fresh numbers).
A failing section is reported and the remaining sections still run, but
the harness then exits nonzero. ``--smoke`` also fails a section that
produces no rows, so CI catches a bit-rotted benchmark.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks.common import print_rows
from repro.launch.compile_cache import use_compile_cache

SECTIONS = [
    ("power", "Table 2: FLyCube power modes & added OAP",
     "benchmarks.power"),
    ("quafl", "Table 3: QuAFL quantization precision sweep",
     "benchmarks.quafl"),
    ("interplane", "Fig 9: inter-plane windows vs plane angle",
     "benchmarks.interplane"),
    ("heatmaps", "Fig 3/13-15: configuration-space heatmaps",
     "benchmarks.heatmaps"),
    ("schedule_gain", "Fig 4/12: scheduling time-to-accuracy",
     "benchmarks.schedule_gain"),
    ("durations", "Fig 11: round-duration summary per algorithm",
     "benchmarks.durations"),
    ("autoflsat_table1", "Table 1: AutoFLSat vs leading alternatives",
     "benchmarks.autoflsat_table1"),
    ("autoflsat_sweep", "Tables 6/7: AutoFLSat cluster/epoch sweep",
     "benchmarks.autoflsat_sweep"),
    ("policy", "Selection-policy sweep: storm + energy scenarios",
     "benchmarks.policy_sweep"),
    ("roofline", "Roofline: per (arch x shape) terms from the dry-run",
     "benchmarks.roofline"),
]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sections", nargs="*",
                    choices=[k for k, _, _ in SECTIONS],
                    help="subset of sections (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="also exit nonzero if a section is empty (CI "
                         "gate); roofline's empty dry-run is tolerated "
                         "via its own self-check")
    args = ap.parse_args()
    want = set(args.sections)
    t0 = time.time()
    failures = []
    for key, title, modname in SECTIONS:
        if want and key not in want:
            continue
        t1 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run(fast=True)
        except Exception as e:  # run the other sections, then exit nonzero
            traceback.print_exc()
            print(f"\n## {title}\nERROR: {type(e).__name__}: {e}")
            failures.append(f"{key}: {type(e).__name__}: {e}")
            continue
        print_rows(f"{title}  [{time.time() - t1:.0f}s]", rows)
        # roofline legitimately yields no rows until a dry-run has been
        # captured; its standalone --smoke self-check covers the math
        if args.smoke and not rows and key != "roofline":
            failures.append(f"{key}: produced no rows")
    print(f"\ntotal: {time.time() - t0:.0f}s")
    if failures:
        raise SystemExit("failed sections:\n  " + "\n  ".join(failures))


if __name__ == "__main__":
    main()
