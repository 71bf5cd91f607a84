"""The comparisons that decide ``correct``, each number with its limit.

A simulator job's answers are its round records (participants, start and
end times, epochs, test accuracy) and its final global model. The
program's records are held against the reference schedule, and one job
drawn from the seed is replayed by the reference from its seed and held
against the program's accuracy per round and final model.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Job:
    """What one simulation job produced: per round (t_start, t_end,
    participants, epochs, accuracy), and the final model's leaves."""
    seed: int
    rounds: List[tuple]
    params: Optional[Dict[str, np.ndarray]] = None


def schedule_numbers(jobs: List[Job], ref_rounds: List[tuple]) -> dict:
    """Participants that differ from the reference schedule (by position,
    plus every participant of a round one side lacks), epoch budgets that
    differ, and the widest gap of a round's start or end time."""
    miss, epochs_off, gap = 0, 0, 0.0
    for job in jobs:
        for i in range(max(len(job.rounds), len(ref_rounds))):
            if i >= len(job.rounds) or i >= len(ref_rounds):
                extra = job.rounds[i] if i < len(job.rounds) \
                    else ref_rounds[i]
                miss += max(len(extra[2]), 1)
                continue
            t0, t1, sel, ep, _ = job.rounds[i]
            r0, r1, rsel, rep = ref_rounds[i][:4]
            miss += sum(a != b for a, b in zip(sel, rsel)) \
                + abs(len(sel) - len(rsel))
            epochs_off += int(float(ep) != float(rep))
            gap = max(gap, abs(t0 - r0), abs(t1 - r1))
    return {"participants_off": miss, "epochs_off": epochs_off,
            "round_time_gap_s": gap}


def model_numbers(job: Job, ref_params: dict, ref_init: dict,
                  ref_acc: List[float]) -> dict:
    """The widest accuracy gap over the job's rounds, and by the worst
    leaf the gap between the norms of the program's and the reference's
    change of the model over the job, against the reference's change of
    that leaf or of the median leaf, whichever is larger. Leaves the
    reference leaves still (change under a thousandth of the median
    leaf's) are not compared. ``model_diff`` is, by the same worst leaf
    and base, the norm of the difference of the two final models."""
    acc = [r[4] for r in job.rounds]
    acc_gap = max((abs(a - b) for a, b in zip(acc, ref_acc)), default=0.0)
    if len(acc) != len(ref_acc):
        acc_gap = 1.0
    ch_ref = {k: _norm(ref_params[k] - ref_init[k]) for k in ref_params}
    med = float(np.median(list(ch_ref.values())))
    worst, diff = 0.0, 0.0
    for k, r in ch_ref.items():
        if r < 1e-3 * med:
            continue
        p = _norm(job.params[k] - ref_init[k])
        worst = max(worst, abs(p - r) / max(r, med))
        diff = max(diff, _norm(job.params[k] - ref_params[k]) / max(r, med))
    return {"accuracy_gap": acc_gap, "model_change_gap": worst,
            "model_diff": diff}


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def verdict(numbers: dict, limits: dict) -> bool:
    """True when every compared number is at or under its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
