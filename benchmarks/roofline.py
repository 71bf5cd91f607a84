"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh)
from the compiled dry-run artifacts in experiments/dryrun/.

  compute_s    = HLO_FLOPs_per_dev / peak_FLOP/s          (197e12 bf16, v5e)
  memory_s     = HLO_bytes_per_dev / HBM_bw               (819e9 B/s)
  collective_s = link_bytes_per_dev / ICI_link_bw         (50e9 B/s)

plus MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference) and the
usefulness ratio MODEL_FLOPS_per_dev / HLO_FLOPs (remat/redundancy waste).

Standalone usage (the harness calls :func:`run`):
    PYTHONPATH=src python benchmarks/roofline.py [--mesh single] [--tag TAG]
        [--out roofline.json] [--smoke]

``--smoke`` runs the built-in self-check — a synthetic dry-run record with
hand-computable terms pushed through :func:`roofline_row` — and tolerates
an empty ``experiments/dryrun/``; without it, missing artifacts are an
error (run ``python -m repro.launch.dryrun`` first). Exits nonzero on any
failure either way (CI smoke gate).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.launch.compile_cache import use_compile_cache

DRYRUN_DIR = pathlib.Path("experiments/dryrun")


def load_records(tag=None, mesh="single"):
    recs = []
    for f in sorted(DRYRUN_DIR.glob("*.json")):
        r = json.loads(f.read_text())
        if r.get("mesh") != mesh:
            continue
        if (tag or "") != r.get("tag", ""):
            continue
        recs.append(r)
    return recs


def roofline_row(r):
    if r["status"] == "skipped":
        return {"arch": r["arch"], "shape": r["shape"],
                "status": "skipped", "compute_s": "", "memory_s": "",
                "collective_s": "", "bottleneck": "",
                "model_vs_hlo": "", "note": r["reason"][:60]}
    if r["status"] != "ok":
        return {"arch": r["arch"], "shape": r["shape"], "status": "ERROR",
                "compute_s": "", "memory_s": "", "collective_s": "",
                "bottleneck": "", "model_vs_hlo": "",
                "note": r.get("error", "")[:60]}
    comp = r["hlo_flops_per_dev"] / PEAK_FLOPS_BF16
    mem = r["hlo_bytes_per_dev"] / HBM_BW
    coll = r["collective_link_bytes_per_dev"] / ICI_BW
    terms = {"compute": comp, "memory": mem, "collective": coll}
    dom = max(terms, key=terms.get)
    model_per_dev = r["model_flops_global"] / r["n_devices"]
    ratio = model_per_dev / max(r["hlo_flops_per_dev"], 1.0)
    return {"arch": r["arch"], "shape": r["shape"], "status": "ok",
            "compute_s": round(comp, 4), "memory_s": round(mem, 4),
            "collective_s": round(coll, 4), "bottleneck": dom,
            "model_vs_hlo": round(ratio, 3),
            "note": f"mem/dev={r['mem_temp_bytes_per_dev'] / 2**30:.1f}GiB"}


def run(fast=True, mesh="single", tag=None):
    return [roofline_row(r) for r in load_records(tag=tag, mesh=mesh)]


# -- standalone CLI ---------------------------------------------------------

#: Synthetic dry-run record whose roofline terms are hand-computable:
#: compute_s = 2.0, memory_s = 1.0, collective_s = 0.5 => compute-bound,
#: and model/HLO usefulness = 0.5.
_SELF_CHECK = {
    "arch": "selfcheck", "shape": "tiny", "mesh": "single", "tag": "",
    "status": "ok", "n_devices": 1,
    "hlo_flops_per_dev": 2.0 * PEAK_FLOPS_BF16,
    "hlo_bytes_per_dev": float(HBM_BW),
    "collective_link_bytes_per_dev": 0.5 * ICI_BW,
    "model_flops_global": PEAK_FLOPS_BF16,
    "mem_temp_bytes_per_dev": 2 ** 30,
}


def self_check() -> list:
    """Push a synthetic record (and the skipped/error shapes) through
    :func:`roofline_row`; any API drift in the row math raises here."""
    row = roofline_row(dict(_SELF_CHECK))
    assert row["compute_s"] == 2.0, row
    assert row["memory_s"] == 1.0, row
    assert row["collective_s"] == 0.5, row
    assert row["bottleneck"] == "compute", row
    assert row["model_vs_hlo"] == 0.5, row
    assert roofline_row({"arch": "a", "shape": "s", "status": "skipped",
                         "reason": "no fit"})["status"] == "skipped"
    assert roofline_row({"arch": "a", "shape": "s", "status": "error",
                         "error": "boom"})["status"] == "ERROR"
    return [row]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--out", default=None,
                    help="also write the rows as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="self-check only gate: tolerate an empty "
                         "experiments/dryrun/ (CI)")
    args = ap.parse_args()
    try:
        rows = self_check()
        real = run(mesh=args.mesh, tag=args.tag)
    except Exception as e:      # any drift vs the dry-run schema fails hard
        print(f"roofline FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        raise SystemExit(1)
    if not real and not args.smoke:
        raise SystemExit(f"no dry-run artifacts under {DRYRUN_DIR}/ — run "
                         "`python -m repro.launch.dryrun` first")
    rows = real or rows         # smoke with no artifacts: the check row
    try:                        # direct `python benchmarks/roofline.py` runs
        from benchmarks.common import print_rows
    except ModuleNotFoundError:
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
        from benchmarks.common import print_rows
    print_rows("Roofline: per (arch x shape) terms"
               + (" [self-check]" if not real else ""), rows)
    if args.out:
        pathlib.Path(args.out).write_text(
            json.dumps({"benchmark": "roofline", "rows": rows}, indent=2)
            + "\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
