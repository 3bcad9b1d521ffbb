"""The trace reduction on a small recorded trace and on plain intervals:
busy time is the union of device operations inside the window, and each
idle gap goes to the host span it fell in."""
import pathlib

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        (DATA / "tiny_trace.textproto").read_text())
    ops, spans = tr.events(pd, [tr.WINDOW_SPAN, "epoch", "put"])
    return tr.reduce(ops, spans)


def test_busy_is_the_union_of_device_ops_inside_the_window(summary):
    assert summary.busy_s == pytest.approx(3500e-9)
    assert summary.window_s == pytest.approx(10000e-9)


def test_device_ops_by_self_time(summary):
    assert dict(summary.device_ops) == pytest.approx(
        {"while.9": 1000e-9, "fusion.1": 1000e-9, "copy.3": 500e-9,
         "scatter.2": 1000e-9})
    assert summary.device_ops[-1][0] == "copy.3"
    assert sum(t for _, t in summary.device_ops) == \
        pytest.approx(summary.busy_s)


def test_self_times_of_nested_events():
    ops = [("loop", 0, 10), ("a", 0, 4), ("b", 5, 7), ("c", 5, 6),
           ("d", 12, 13)]
    assert tr.self_times(ops) == [("loop", 4), ("a", 4), ("b", 1),
                                  ("c", 1), ("d", 1)]


def test_idle_gaps_attributed_to_host_spans(summary):
    assert dict(summary.idle_gaps) == pytest.approx(
        {"put": 3000e-9, "between calls": 2500e-9, "epoch": 1000e-9})
    assert [n for n, _ in summary.idle_gaps] == \
        ["put", "between calls", "epoch"]


@pytest.mark.parametrize("intervals,expected", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(3, 3), (4, 2)], []),
])
def test_union(intervals, expected):
    assert tr.union(intervals) == expected


def test_gaps():
    busy = tr.union([(0, 2), (4, 6), (9, 10)])
    assert tr.gaps(busy, 0, 10) == [(2, 4), (6, 9)]
    assert tr.gaps(busy, -1, 12) == [(-1, 0), (2, 4), (6, 9), (10, 12)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_innermost_span_wins():
    idx = tr.SpanIndex({"outer": [(0, 100)], "inner": [(10, 20), (30, 40)]})
    assert idx.innermost(15, "none") == "inner"
    assert idx.innermost(25, "none") == "outer"
    assert idx.innermost(150, "none") == "none"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="window"):
        tr.reduce([[("op", 0, 1)]], {tr.WINDOW_SPAN: []})
    with pytest.raises(ValueError, match="device"):
        tr.reduce([], {tr.WINDOW_SPAN: [(0, 1)]})


@pytest.mark.parametrize("raw,name", [
    ("%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128] %p)",
     "fusion.12"),
    ("copy.3", "copy.3"),
])
def test_op_names_are_shortened_from_hlo_text(raw, name):
    assert tr.op_name(raw) == name
