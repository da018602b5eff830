"""The trace reduction: on events with known answers, and on a trace
recorded on one TPU v5e (a dense-engine MLP run of the benchmark)."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from benchmarks.chip import trace

FIXTURE = (pathlib.Path(__file__).parent / "data"
           / "mlp_dense_v5e.xplane.pb.gz")
SPANS = ("mlp.query",)


def synthetic() -> trace.Events:
    # window 0..100; device 0 busy 10-40 (op b nested in a) and 60-70;
    # device 1 busy 0-50
    ops = {0: [(10, 40, "a"), (25, 35, "b"), (60, 70, "a")],
           1: [(0, 50, "c")]}
    spans = [(0, 100, trace.WINDOW_SPAN),
             (0, 45, "outer"), (42, 55, "inner"), (80, 90, "late")]
    return trace.Events(ops=ops, spans=spans)


def test_union_merges_overlaps_and_clips():
    assert trace.union([(10, 30), (25, 40), (60, 70)], 0, 65) == [
        (10, 40), (60, 65)]
    assert trace.gaps([(10, 40), (60, 70)], 0, 100) == [
        (0, 10), (40, 60), (70, 100)]


def test_busy_idle_and_attribution_by_hand():
    ev = synthetic()
    out = trace.reduce(ev, *trace.window(ev))
    # device 0 busy 30 + 10 = 40 ns, device 1 50 ns: mean 45 ns of 100
    assert out["busy_s"] == pytest.approx(45e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_share"] == pytest.approx(0.55)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # device 0 idle 0-10 (outer), 40-60: 40-42 outer, 42-55 inner (the
    # shorter span), 55-60 none; 70-100: 80-90 late, the rest none
    assert gaps == pytest.approx({"outer": 12e-9, "inner": 13e-9,
                                  "late": 10e-9, trace.NO_SPAN: 25e-9})
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"a": 30e-9, "b": 10e-9})


def test_nested_ops_count_their_self_time():
    ops = [(0, 100, "while"), (10, 30, "fusion"), (40, 50, "fusion"),
           (60, 90, "copy"), (120, 130, "fusion")]
    assert trace.self_times(ops, 0, 125) == {"while": 40, "fusion": 35,
                                             "copy": 30}


def test_op_names_are_the_instruction_names():
    assert trace.op_name("%fusion.54 = f32[2,3]{1,0} fusion(f32[2] %a)") \
        == "fusion.54"
    assert trace.op_name("copy.1") == "copy.1"


def test_window_must_be_unique():
    ev = synthetic()
    ev.spans.append((0, 1, trace.WINDOW_SPAN))
    with pytest.raises(ValueError):
        trace.window(ev)


@pytest.fixture(scope="module")
def recorded():
    ev = trace.load(str(FIXTURE), SPANS)
    return ev, trace.window(ev)


def test_recorded_trace_has_device_ops_and_host_spans(recorded):
    ev, (lo, hi) = recorded
    assert list(ev.ops) == [0] and len(ev.ops[0]) > 100
    assert sum(n == "mlp.query" for _, _, n in ev.spans) > 10
    assert hi - lo > 1e8            # a window of over 0.1 s


def test_recorded_trace_reduction_agrees_with_a_timeline(recorded):
    ev, (lo, hi) = recorded
    out = trace.reduce(ev, lo, hi)
    # the busy time again, on a 100 ns grid
    grid = np.zeros(int((hi - lo) // 100) + 1, bool)
    for a, b, _ in ev.ops[0]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 100):int(np.ceil((b - lo) / 100))] = True
    assert out["busy_s"] == pytest.approx(grid.sum() * 100e-9, rel=2e-2)
    idle = sum(v for _, v in out["breakdown"]["idle_gaps"])
    if len(out["breakdown"]["idle_gaps"]) < trace.TOP:
        assert idle == pytest.approx(out["window_s"] - out["busy_s"],
                                     rel=1e-9)
    assert 0 < out["idle_share"] < 1
    names = {n for n, _ in out["breakdown"]["idle_gaps"]}
    assert names <= {"mlp.query", trace.NO_SPAN}
