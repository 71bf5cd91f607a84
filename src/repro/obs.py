"""The program's one span-and-counter recorder.

``span(name)`` times a block of host code. It opens
``jax.profiler.TraceAnnotation(name)``, so a profiled run shows the span
on the profiler's host plane, on the same clock as the device planes,
and it adds the block's wall nanoseconds to the active ``RunTrace``:
count, total time, and self time (total less its child spans).
``count(name, n)`` adds to a counter of the active run. ``sync(x)`` is
the one way the FL round path reads device values onto the host.

JAX dispatch is asynchronous, so a span's time is host time: the span
``fl.sync`` that ``sync`` opens absorbs all device work queued before
the read. Every other span's self time is the host's too, including any
time a dispatch inside it blocks.

A ``RunTrace`` covers one FL engine, its construction and its ``run()``;
the engine activates it with ``recording`` and ``traced_run``. The LM
trainer's ``run_hfl_rounds`` records one per call through
``logged_run``. Finished runs go to a bounded in-process log, and
``runs(seeds)`` merges the runs of some seeds (the points of a sweep, or
the calls of a benchmark window). Compilations are counted
through ``jax.monitoring`` and keyed by the innermost open span. The
recorder keeps module state and assumes one thread drives the engines.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time
from typing import Dict, Iterable, List, Optional

import jax

#: Finished runs kept in the in-process log.
LOG_SIZE = 1024

# JAX's compile events (``jax._src.dispatch``): tracing to a jaxpr,
# lowering to an MLIR module (once per program, compiled or loaded from
# the persistent cache) and the backend step, which holds either XLA's
# compilation or the persistent cache's read (timed alone by
# ``CACHE_EVENT``, so that one is not added to ``compile_s`` again).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_now = time.perf_counter_ns
_annotation = jax.profiler.TraceAnnotation
_active: List["RunTrace"] = []          # runs being recorded, innermost last
_open: List["span"] = []                # open spans, innermost last
_log: collections.deque = collections.deque(maxlen=LOG_SIZE)


class RunTrace:
    """Spans and counters of one engine: its FL ``seed`` and
    ``algorithm``; ``spans`` maps a name to ``[count, total_ns,
    self_ns]``; ``counters`` maps a name to a number; ``round_ns`` holds
    the host wall time of each completed round; ``events`` is the run's
    ``repro.sim.events.EventStats`` (the engine's ``event_stats``)."""

    def __init__(self, seed, algorithm: str):
        self.seed, self.algorithm = seed, algorithm
        self.spans: Dict[str, List[int]] = {}
        self.counters: Dict[str, float] = {}
        self.round_ns: List[int] = []
        self.events = None
        self._mark = None

    @property
    def rounds(self) -> int:
        return len(self.round_ns)

    def round_done(self) -> None:
        """Close a round: its wall time runs from the previous round's
        close, or from the start of ``run()``."""
        now = _now()
        self.round_ns.append(now - self._mark)
        self._mark = now

    def per_round_ms(self, name: str) -> Optional[float]:
        """Self milliseconds of span ``name`` per completed round; None if
        the span never opened or no round completed."""
        st = self.spans.get(name)
        if st is None or not self.round_ns:
            return None
        return st[2] / self.rounds / 1e6

    def summary(self) -> dict:
        """Where the host's wall time went: per-round wall milliseconds
        (mean and p95), each span's self milliseconds per round (largest
        first), the counters and the event counts."""
        wall = sorted(self.round_ns)
        ms = sorted(((k, self.per_round_ms(k)) for k in self.spans),
                    key=lambda kv: -kv[1]) if wall else []
        return {"seed": self.seed, "algorithm": self.algorithm,
                "rounds": self.rounds,
                "round_ms": sum(wall) / len(wall) / 1e6 if wall else None,
                "round_p95_ms": wall[min(int(0.95 * len(wall)),
                                         len(wall) - 1)] / 1e6
                if wall else None,
                "self_ms_per_round": dict(ms),
                "counters": dict(self.counters),
                "events": self.events.as_dict() if self.events is not None
                else {}}

    @classmethod
    def merge(cls, traces: Iterable["RunTrace"]) -> "RunTrace":
        """One trace summing ``traces``: spans, counters and events added,
        rounds concatenated; ``seed`` is the tuple of their seeds."""
        traces = list(traces)
        out = cls(tuple(t.seed for t in traces),
                  "+".join(sorted({t.algorithm for t in traces})))
        for t in traces:
            for k, v in t.spans.items():
                st = out.spans.setdefault(k, [0, 0, 0])
                for i in range(3):
                    st[i] += v[i]
            for k, v in t.counters.items():
                out.counters[k] = out.counters.get(k, 0) + v
            out.round_ns += t.round_ns
            if t.events is not None:
                if out.events is None:
                    out.events = type(t.events)()
                for k, v in t.events.counts.items():
                    out.events.add(k, v)
                out.events.batched_passes += t.events.batched_passes
        return out

    def __repr__(self):
        return (f"RunTrace(seed={self.seed!r}, {self.algorithm}, "
                f"rounds={self.rounds}, spans={sorted(self.spans)})")


class span:
    """Context manager timing a block as the span ``name`` (see the
    module's docstring). After the block, ``ns`` is its wall time."""

    __slots__ = ("name", "ns", "_ann", "_t0", "_child")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._child = 0
        _open.append(self)
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        ns = self.ns = _now() - self._t0
        _open.pop()
        if _open:
            _open[-1]._child += ns
        if _active:
            st = _active[-1].spans.get(self.name)
            if st is None:
                st = _active[-1].spans[self.name] = [0, 0, 0]
            st[0] += 1
            st[1] += ns
            st[2] += ns - self._child
        self._ann.__exit__(*exc)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the active run's counter ``name``."""
    if _active:
        c = _active[-1].counters
        c[name] = c.get(name, 0) + n


def sync(x):
    """The host value of device value(s) ``x``, read in the span
    ``fl.sync`` and counted as ``host_syncs``."""
    with span("fl.sync"):
        count("host_syncs")
        return jax.device_get(x)


@contextlib.contextmanager
def recording(trace: RunTrace):
    """Record spans and counters into ``trace`` inside the block."""
    _active.append(trace)
    try:
        yield trace
    finally:
        _active.pop()


@contextlib.contextmanager
def logged_run(trace: RunTrace, name: str):
    """Record the block into ``trace`` under the span ``name``, start its
    round clock, and log the trace when the block ends (also when it
    raises)."""
    try:
        with recording(trace), span(name):
            trace._mark = _now()
            yield trace
    finally:
        _log.append(trace)


def traced_run(method):
    """Decorator of an engine's ``run``: the call is ``logged_run`` of the
    engine's ``trace`` under the span ``fl.run``."""
    @functools.wraps(method)
    def run(self, *args, **kwargs):
        with logged_run(self.trace, "fl.run"):
            return method(self, *args, **kwargs)
    return run


def runs(seeds: Iterable) -> Optional[RunTrace]:
    """The newest logged run of each FL seed in ``seeds``, merged into one
    trace; None if any seed has no logged run."""
    latest = {}
    for t in reversed(_log):
        latest.setdefault(t.seed, t)
    want = set(seeds)
    if not want or not want <= latest.keys():
        return None
    return RunTrace.merge(latest[s] for s in want)


_COMPILE_EVENTS = frozenset((TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT,
                             CACHE_EVENT))


def _on_compile(event: str, seconds: float, **_) -> None:
    if not _active or event not in _COMPILE_EVENTS:
        return
    where = _open[-1].name if _open else "(no span)"
    if event == CACHE_EVENT:
        count("cache_load_s", seconds)
        return
    if event == LOWER_EVENT:
        count("compiles")
        count("compiles." + where)
    count("compile_s", seconds)
    count("compile_s." + where, seconds)


jax.monitoring.register_event_duration_secs_listener(_on_compile)
