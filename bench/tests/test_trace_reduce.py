"""Trace reduction on a small hand-recorded trace."""
import pytest

import trace_reduce as tr

D0, D1, H = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE
EVENTS = [
    (H, "python", "bench.window", 1000.0, 9000.0),
    (H, "python", "bench.invoke", 1500.0, 3000.0),
    (D0, tr.OPS_LINE, "fusion.1", 0.0, 2000.0),        # half outside
    (D0, tr.OPS_LINE, "scatter.2", 1500.0, 1000.0),    # overlaps fusion.1
    (D0, tr.OPS_LINE, "fusion.1", 6000.0, 1000.0),
    (D0, tr.MODULES_LINE, "jit_field_fn_fused(7)", 1000.0, 1500.0),
    (D0, tr.MODULES_LINE, "jit_field_fn_fused(7)", 6000.0, 1000.0),
    (D0, tr.MODULES_LINE, "jit_other(1)", 7500.0, 100.0),
    (D1, tr.OPS_LINE, "fusion.1", 2000.0, 4000.0),
]


def test_union_and_clip():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [
        (0, 3), (5, 7)]
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_busy_averages_over_devices():
    win = tr.host_span(EVENTS, "bench.window")
    assert win == (1000.0, 10000.0)
    b = tr.busy(EVENTS, win)
    # TPU:0 busy 1000-2500 and 6000-7000, TPU:1 busy 2000-6000
    assert b["per_device"][D0] == [(1000.0, 2500.0), (6000.0, 7000.0)]
    assert b["busy_s"] == pytest.approx((2500 + 4000) / 2 * 1e-9)
    assert b["window_s"] == pytest.approx(9000e-9)


def test_top_ops_and_modules():
    win = (1000.0, 10000.0)
    ops = dict(tr.top_ops(EVENTS, win))
    assert ops["fusion.1"] == pytest.approx((1000 + 1000 + 4000) / 2 * 1e-9)
    assert ops["scatter.2"] == pytest.approx(1000 / 2 * 1e-9)
    assert tr.module_times(EVENTS, win, "field_fn") == pytest.approx(
        [1500e-9, 1000e-9])


def test_idle_gaps_named_by_host_span():
    win = (1000.0, 10000.0)
    busy = tr.busy(EVENTS, win)["per_device"][D0]
    host = [("bench.invoke", 1500.0, 4500.0),
            ("invocation.swap", 2600.0, 5900.0)]
    gaps = tr.idle_gaps(busy, win, host)
    assert gaps[0] == ["invocation.swap", pytest.approx(3500e-9)]
    assert gaps[1] == ["no host span", pytest.approx(3000e-9)]


def test_idle_gap_named_by_innermost_span_covering_half():
    """A gap inside an enclosing annotation is named by the shorter span
    that covers most of it, not by the annotation that covers all of it."""
    win = (0.0, 100.0)
    host = [("bench.invoke", 0.0, 100.0), ("invocation.swap", 10.0, 80.0),
            ("invocation.field", 82.0, 84.0)]
    gaps = tr.idle_gaps([(0.0, 5.0), (85.0, 100.0)], win, host)
    assert gaps == [["invocation.swap", pytest.approx(80e-9)]]


def test_program_spans_onto_profiler_clock():
    spans = [{"name": "invocation.swap", "t0": 10.5, "t1": 12.0},
             {"name": "open", "t0": 11.0, "t1": None}]
    assert tr.to_profiler_clock(spans, anchor_mono=10.0, anchor_ns=1000.0) \
        == [("invocation.swap", 1000.0 + 0.5e9, 1000.0 + 2.0e9)]
