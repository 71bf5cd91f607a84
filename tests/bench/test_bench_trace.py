"""The trace reduction, on a small trace recorded on the CPU.

The CPU trace has no device plane, so the tests name the host plane as
the device, its XLA client thread as the operations line and the Python
thread's dispatch events as the launches."""
import pathlib
import re

import jax
import pytest

from bench import trace

TRACE = pathlib.Path(__file__).resolve().parent / "data" / \
    "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    return jax.profiler.ProfileData.from_file(str(TRACE))


def lines(pd):
    plane = pd.find_plane_with_name(trace.HOST_PLANE)
    return {line.name: line for line in plane.lines}


def reduce(pd):
    ops = next(n for n in lines(pd) if n.startswith("tf_XLAPjRtCpuClient"))
    lo, hi = trace.window_of(pd, "bench.window")
    return trace.summarize(pd, lo, hi, device_plane=re.compile("^/host:CPU$"),
                           ops_line=ops, modules_line="python"), ops, lo, hi


def test_merge_and_clip():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ev = [trace.Event("a", 0, 10), trace.Event("b", 20, 30)]
    assert trace.clip(ev, 5, 25) == [trace.Event("a", 5, 10),
                                     trace.Event("b", 20, 25)]


def test_window_of_finds_the_span(pd):
    lo, hi = trace.window_of(pd, "bench.window")
    assert hi > lo
    with pytest.raises(ValueError):
        trace.window_of(pd, "no such span")


def test_busy_is_the_union_of_operations(pd):
    s, ops, lo, hi = reduce(pd)
    evs = trace.clip(trace.events(lines(pd)[ops]), lo, hi)
    union = sum(b - a for a, b in trace.merge([(e.start, e.end)
                                               for e in evs]))
    assert s.devices == 1
    assert s.busy_s == pytest.approx(union * 1e-9)
    assert 0 < s.busy_s < s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s.idle_share() < 1
    assert s.seconds_of("dot_general", s.op_s) > 0


def test_launches_and_program_names(pd):
    s, _, lo, hi = reduce(pd)
    n = sum(1 for e in trace.clip(trace.events(lines(pd)["python"]), lo, hi)
            if e.name == "PjitFunction(probe)")
    assert n >= 3
    assert s.program_launches["PjitFunction(probe)"] == n
    assert trace.program_name("jit__local_sgd_batch(12)") == \
        "jit__local_sgd_batch"


def test_gaps_and_busy_cover_the_window(pd):
    s, *_ = reduce(pd)
    assert sum(g for _, g in s.gaps) + s.busy_s == pytest.approx(s.window_s)
    assert all(name for name, _ in s.gaps)
    b = s.breakdown()
    for key in ("device_ops", "idle_gaps"):
        secs = [v for _, v in b[key]]
        assert 0 < len(secs) <= 10 and secs == sorted(secs, reverse=True)
