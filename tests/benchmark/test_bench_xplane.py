"""The reduction from a profiler trace to busy time, the
heaviest operations and the named idle gaps, on small recorded traces."""

import glob
import os

import pytest

import bench_tiny  # noqa: F401
from benchmark.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        return xplane.Trace.from_profile(ProfileData.from_text_proto(f.read()))


@pytest.fixture(scope="module")
def trace():
    return load("synthetic_trace.textproto")


def test_window_and_annotations(trace):
    assert trace.window() == (0.0, 10000.0)
    assert trace.spans("bench.split") == [(0.0, 900.0), (6000.0, 10000.0)]
    assert all(n.startswith("bench.") for n, _, _ in trace.annotations)


def test_busy_is_the_union_not_the_sum(trace):
    lo, hi = trace.window()
    # while [1000, 5000) covers its children; copy [7000, 8000)
    assert trace.busy_ns(lo, hi) == 5000.0
    assert trace.busy_ns(2000.0, 7500.0) == 3500.0
    busy, fits = trace.busy_within("bench.fit")
    assert (busy, fits) == (4000.0, 1)


def test_own_time_and_heaviest_operations(trace):
    lo, hi = trace.window()
    own = dict(xplane.self_times(trace.device_ops[0]))
    assert own[[n for n in own if n.startswith("%while.1")][0]] == 1500.0
    top = trace.top_ops(lo, hi, k=3)
    # the while's own time (its body's operations taken out) ties with the
    # kernel's at 1500 ns; the fusion and the copy have 1000 ns each
    assert {n.split()[0] for n, _ in top[:2]} == {"%while.1",
                                                  "%custom-call.3"}
    assert [s for _, s in top] == pytest.approx([1.5e-6, 1.5e-6, 1e-6])
    assert any("custom-call tpu_custom_call" in n for n, _ in top)


def test_idle_gaps_are_named_by_what_the_host_was_doing(trace):
    lo, hi = trace.window()
    gaps = dict(trace.idle_gaps(lo, hi))
    # every gap of the tiny trace is under 50 us: one label takes them all
    assert gaps == {"gaps_under_50_us": pytest.approx(5000.0 / 1e9)}
    wide = xplane.Trace([[(n, a * 100, b * 100)
                          for n, a, b in trace.device_ops[0]]],
                        [(n, a * 100, b * 100)
                         for n, a, b in trace.annotations])
    named = dict(wide.idle_gaps(lo * 100, hi * 100,
                                extra=[("quantize", 5.5e5, 6.5e5)]))
    # gap [0, 1000): split to 900, then fit. Gap [5000, 7000): fit to
    # 5500, quantize (the program's span began later) to 6000, then the
    # split that began at 6000. Gap [8000, 10000): split. (x 100 ns)
    assert named["split"] == pytest.approx((900 + 1000 + 2000) * 100 / 1e9)
    assert named["fit"] == pytest.approx((100 + 500) * 100 / 1e9)
    assert named["quantize"] == pytest.approx(500 * 100 / 1e9)
    assert sum(named.values()) == pytest.approx(
        (wide.window()[1] - wide.busy_ns(*wide.window())) / 1e9)


@pytest.mark.parametrize("intervals,want", [
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(0, 5), (1, 2), (3, 9)], [(0, 9)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(-5, 2), (8, 20)], [(0, 2), (8, 10)]),
    ([], []),
])
def test_union(intervals, want):
    assert xplane.union(intervals, 0, 10) == want


@pytest.mark.parametrize("name,want", [
    ("%fusion.471 = f32[851968]{0:T(1024)S(1)} fusion(f32[127]{0:T(128)S(1)} "
     "%a, s32[851968]{0:T(1024)S(1)} %b), kind=kCustom, calls=%c",
     "%fusion.471 f32[851968] fusion kCustom"),
    ("%broadcast.6415 = s32[851968,10,64]{0,2,1:T(8,128)} broadcast("
     "s32[851968,10]{0,1:T(8,128)} %g), dimensions={0,1}",
     "%broadcast.6415 s32[851968,10,64] broadcast"),
    ("dot_general.1", "dot_general.1"),
])
def test_short_op_name(name, want):
    assert xplane.short_op_name(name) == want


def test_a_trace_without_one_window_is_refused(trace):
    broken = xplane.Trace(trace.device_ops,
                          [a for a in trace.annotations
                           if a[0] != xplane.WINDOW])
    with pytest.raises(ValueError):
        broken.window()


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "recorded_*.textproto"))) or [None])
def test_recorded_chip_trace_reduces(path):
    """A cut of a trace recorded on the v5e (`tools.py trace-cut`)."""
    if path is None:
        pytest.skip("no recorded trace in tests/benchmark/data")
    t = load(os.path.basename(path))
    lo, hi = t.window()
    busy = t.busy_ns(lo, hi)
    assert 0 < busy <= hi - lo
    top = t.top_ops(lo, hi)
    assert top and top[0][1] > 0
    assert sum(s for _, s in top) <= busy / 1e9 * (1 + 1e-9)
    idle = sum(s for _, s in t.idle_gaps(lo, hi, k=10**6))
    assert idle == pytest.approx((hi - lo - busy) / 1e9)
