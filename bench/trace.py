"""Reduction of a JAX profiler trace (xplane) to the benchmark's numbers.

A trace holds planes (one per device, one for the host) of lines of timed
events. On a TPU each device plane has a line of program executions
("XLA Modules": one event per launch of a compiled program) and a line of
the operations inside them ("XLA Ops"). The reduction takes, inside one
window of the host's clock:

- busy time: the union of the device's operation intervals;
- launches and device time per program, and device time per operation;
- the idle gaps between busy intervals, each named by the innermost host
  event (a benchmark span, a JAX dispatch, a transfer, a compile) that
  was open at the gap's middle.

Several devices are averaged. Nothing here knows about a particular
program: readers in ``bench/metrics`` pick programs and kernels by name.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns


def program_name(name: str) -> str:
    """A launch's program name without the run id some lines append."""
    return _SUFFIX.sub("", name).strip()


def events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(evs: List[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in evs if e.end > lo and e.start < hi]


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float
    busy_s: float                          # mean over devices
    launches: float                        # program launches per device
    program_s: Dict[str, float]            # device seconds per program
    program_launches: Dict[str, float]
    op_s: Dict[str, float]                 # device seconds per operation
    gaps: List[Tuple[str, float]]          # (host activity, seconds), device 0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, pattern: str, table: Optional[dict] = None) -> float:
        """Device seconds of every program (or ``table``'s entries) whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        table = self.program_s if table is None else table
        return sum(v for k, v in table.items() if rx.search(k))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_host = collections.Counter()
        for name, s in self.gaps:
            by_host[name] += s
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def host_spans(pd, plane_name: str = HOST_PLANE) -> List[Event]:
    plane = pd.find_plane_with_name(plane_name)
    if plane is None:
        return []
    return [e for line in plane.lines for e in events(line)
            if e.end > e.start]


def window_of(pd, span: str, plane_name: str = HOST_PLANE
              ) -> Tuple[float, float]:
    """(start, end) in ns of the host span named ``span``."""
    found = [e for e in host_spans(pd, plane_name) if e.name == span]
    if not found:
        raise ValueError(f"no host span {span!r} in the trace")
    return found[0].start, found[0].end


def _gap_namer(host: List[Event]):
    """A function naming a gap by the innermost host event open at its
    middle (vectorized: a window holds many gaps and host events)."""
    names = [program_name(e.name) for e in host]
    start = np.array([e.start for e in host])
    end = np.array([e.end for e in host])
    length = end - start

    def name(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        inner = np.nonzero((start <= mid) & (mid < end))[0]
        if not len(inner):
            return "(no host event)"
        return names[inner[np.argmin(length[inner])]]
    return name


def summarize(pd, lo: float, hi: float, device_plane=DEVICE_PLANE,
              ops_line: str = OPS_LINE, modules_line: str = MODULES_LINE,
              host_plane: str = HOST_PLANE) -> Summary:
    """Reduce the trace ``pd`` (a ``jax.profiler.ProfileData``) to a
    ``Summary`` of the window [lo, hi) ns. ``device_plane`` matches the
    planes counted as devices."""
    planes = [p for p in pd.planes if device_plane.search(p.name)]
    if not planes:
        raise ValueError("the trace has no device plane")
    name_gap = _gap_namer(clip(host_spans(pd, host_plane), lo, hi))
    busy, launches = 0.0, 0.0
    program_s: Dict[str, float] = collections.Counter()
    program_n: Dict[str, float] = collections.Counter()
    op_s: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[str, float]] = []
    for i, plane in enumerate(planes):
        lines = {line.name: line for line in plane.lines}
        ops = clip(events(lines[ops_line]), lo, hi) if ops_line in lines \
            else []
        mods = clip(events(lines[modules_line]), lo, hi) \
            if modules_line in lines else []
        merged = merge([(e.start, e.end) for e in (ops or mods)])
        busy += sum(b - a for a, b in merged)
        launches += len(mods)
        for e in mods:
            program_s[program_name(e.name)] += (e.end - e.start) * 1e-9
            program_n[program_name(e.name)] += 1
        for e in ops:
            op_s[program_name(e.name)] += (e.end - e.start) * 1e-9
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((name_gap(a, b), (b - a) * 1e-9))
    n = len(planes)
    scale = lambda d: {k: v / n for k, v in d.items()}
    return Summary(devices=n, window_s=(hi - lo) * 1e-9,
                   busy_s=busy * 1e-9 / n, launches=launches / n,
                   program_s=scale(program_s),
                   program_launches=scale(program_n), op_s=scale(op_s),
                   gaps=gaps)
