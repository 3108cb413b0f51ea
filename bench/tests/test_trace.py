"""The trace reduction on a small trace recorded on a TPU v5e: three calls of
a jitted matmul-tanh-matmul, each followed by a 2 ms host sleep inside a
``bench.handover`` span, all inside ``bench.window``.  In this trace the
device's clock runs 1.1 ms behind the host's (each program appears to start
before its launch), which the reduction corrects."""
import os

import pytest

from bench import trace as tr

PATH = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return tr.reduce(PATH)


def test_window_is_the_harness_span(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.010746509, rel=1e-9)
    names = {name for _, _, name in red.spans}
    assert {"bench.window", "bench.call", "bench.handover"} <= names


def test_busy_is_the_union_of_leaf_ops(red):
    assert len(red.ops) == 12
    assert red.busy_s == pytest.approx(7.2611e-05, rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    merged = tr._union([(s, e) for s, e, _ in red.ops])
    assert red.busy_ns == pytest.approx(sum(e - s for s, e in merged))


def test_top_ops_and_kernel_time(red):
    top = red.top_ops(10)
    assert len(top) <= 10 and top[0][1] >= top[-1][1]
    assert {name for name, _ in top} >= {"fusion", "convolution_tanh_fusion"}
    n, secs = red.kernel(r"^%?convolution_tanh_fusion = ")
    assert n == 3 and secs == pytest.approx(dict(top)["convolution_tanh_fusion"])
    assert red.kernel(r"ssd_scan") == (0, 0.0)


def test_device_clock_is_aligned_to_the_launches(red):
    s, e = red.window
    assert all(s <= a and b <= e for a, b, _ in red.ops)
    calls = sorted((a, b) for a, b, name in red.spans if name == "bench.call")
    starts = sorted(a for a, _, text in red.ops if text.startswith("%convolution_tanh_fusion"))
    assert all(c0 <= d <= c1 for (c0, c1), d in zip(calls, starts))


def test_idle_gaps_are_named_by_host_span(red):
    gaps = red.idle_gaps(10)
    assert len(gaps) <= 10
    assert [g[0] for g in gaps[:3]] == ["bench.handover"] * 3 and gaps[2][1] > 2e-3
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    total_idle = sum(e - s for s, e in red.gaps) * 1e-9
    assert total_idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_leaves_drop_enclosing_events():
    events = [(0, 100, "%while.1 = while"), (10, 20, "%a = f"), (30, 40, "%b = g"), (120, 130, "%c = h")]
    assert [e[2] for e in tr._leaves(events)] == ["%a = f", "%b = g", "%c = h"]
