"""The trace reduction on a small trace recorded on the CPU.

``data/cpu_window.xplane.pb`` (see ``data/record_cpu_trace.py``) holds a
``bench:window`` span with jitted loops and a 200 ms sleep.  On the CPU
XLA's ops run on the PjRt client's host threads, so those lines stand
in for a device's op line.
"""
from pathlib import Path

import pytest

from bench.trace_reduce import (NO_SPAN, TOP, _leaves, _op_name, _union,
                                 reduce_trace)

TRACE = Path(__file__).with_name("data") / "cpu_window.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE, device_plane="/host:CPU",
                        op_line="tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    spans, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                if ev.name == "bench:window":
                    spans.append(iv)
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.append(iv)
    return spans[0], ops


def test_window_is_the_window_span(reduced, raw):
    (lo, hi), _ = raw
    assert reduced["window_s"] == pytest.approx((hi - lo) / 1e9, abs=1e-9)


def test_busy_is_the_union_of_ops_in_the_window(reduced, raw):
    (lo, hi), ops = raw
    clipped = [(max(a, lo), min(b, hi)) for a, b in ops if b > lo and a < hi]
    busy = sum(b - a for a, b in _union(clipped)) / 1e9
    assert reduced["busy_s"] == pytest.approx(busy, abs=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_idle_gaps_add_up_and_name_the_sleep(reduced):
    gaps = reduced["idle_gaps"]
    total = sum(s for _, s in gaps)
    assert total == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                  rel=1e-6)
    assert gaps[0][0] == "bench:sleep" and gaps[0][1] > 0.19
    assert NO_SPAN not in dict(gaps) or dict(gaps)[NO_SPAN] < 0.01


def test_ops_are_ranked_and_capped(reduced):
    secs = [s for _, s in reduced["device_ops"]]
    assert 0 < len(secs) <= TOP
    assert secs == sorted(secs, reverse=True)


def test_union_merges_overlaps():
    assert _union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="no 'nowhere' span"):
        reduce_trace(TRACE, device_plane="/host:CPU",
                     op_line="tf_XLAPjRtCpuClient", window_span="nowhere")


def test_ops_that_hold_others_are_not_counted_twice():
    ops = [(0, 100, "%while.1 = (...) while(...)"), (10, 40, "%fusion.2 = f"),
           (50, 90, "%fusion.3 = g"), (120, 130, "%copy.4 = h")]
    assert [_op_name(e[2]) for e in _leaves(ops)] == ["fusion.2",
                                                      "fusion.3", "copy.4"]
