"""The per-layer reductions of `layers.py` on synthetic events, on a
small trace with the program's spans and on spans cut from a TPU v5e
trace: a span's self time leaves out the named spans nested in it, and
every device op lands in exactly one scope bucket (a fusion under a
transform-wrapped scope, an op without one as `unscoped`), so the
buckets add up to the busy time."""
import json
import pathlib

import pytest

import layers
import trace_reduce
from repro.trace import spans

DATA = pathlib.Path(__file__).resolve().parent / "data"

HLO = "\n".join([
    "ENTRY %main (p: s32[]) -> s32[] {",
    '  %while.9 = (s32[]) while(%p), metadata={op_name="jit(epoch)/'
    'vmap()/while"}',
    '  %fusion.1 = s32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
    'metadata={op_name="jit(epoch)/vmap()/while/body/closed_call/'
    'vmap(tick.leader)/mul"}',
    "  %copy.2 = s32[4]{0} copy(%fusion.1)",
    '  ROOT %fusion.3 = s32[4]{0} fusion(%copy.2), kind=kLoop, calls=%g, '
    'metadata={op_name="jit(epoch)/vmap(epoch.compact)/rev"}',
    "}"])


def test_span_self_time_leaves_out_nested_spans():
    host = {"window": [(0, 100)],
            "put": [(10, 60)], "get": [(70, 90)],
            "kv.tick": [(12, 20)],
            "kv.sync": [(25, 30), (40, 45), (72, 74)],
            "kv.write": [(95, 120)]}          # cut by the window's end
    got = layers.span_self_s(host, 0, 100)
    assert got == pytest.approx({
        "window": 25e-9, "put": 32e-9, "get": 18e-9, "kv.tick": 8e-9,
        "kv.sync": 12e-9, "kv.write": 5e-9})
    assert sum(got.values()) == pytest.approx(100e-9)


def test_every_device_op_lands_in_one_scope_bucket():
    scope_of_op = spans.hlo_op_scopes(HLO)
    ops = [("while.9", 1000, 5000), ("fusion.1", 1000, 3000),
           ("copy.2", 3000, 4000), ("fusion.3", 5000, 6000),
           ("fusion.3", 9000, 12000)]         # half outside the window
    got = layers.scope_device_s([ops, ops], scope_of_op, 0, 10000)
    assert set(got) == set(spans.SCOPES) | {spans.UNSCOPED}
    want = {s: 0.0 for s in got}
    want.update({"tick.leader": 2000e-9, "epoch.compact": 2000e-9,
                 spans.UNSCOPED: 2000e-9})        # the loop's own, copy.2
    assert got == pytest.approx(want)
    busy = trace_reduce.total(trace_reduce.union(
        [(max(s, 0), min(e, 10000)) for _, s, e in ops if s < 10000]))
    assert sum(got.values()) == pytest.approx(busy * 1e-9)


@pytest.fixture(scope="module")
def managed():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        (DATA / "tiny_layers.textproto").read_text())
    return layers.breakdown(pd, ("epoch",), spans.hlo_op_scopes(HLO))


def test_breakdown_of_a_traced_epoch(managed):
    assert managed["window_s"] == pytest.approx(10000e-9)
    assert managed["busy_s"] == pytest.approx(5000e-9)
    assert {k: v for k, v in managed["span_self_s"].items() if v} == \
        pytest.approx({"window": 1000e-9, "epoch": 500e-9,
                       "fleet.dispatch": 500e-9, "fleet.fetch": 5500e-9,
                       "fleet.control": 2000e-9, "fleet.writeback": 500e-9})
    assert managed["span_counts"]["fleet.fetch"] == 1
    assert managed["span_counts"]["kv.sync"] == 0
    scoped = {k: v for k, v in managed["scope_device_s"].items() if v}
    assert scoped == pytest.approx({"tick.leader": 2000e-9,
                                    "epoch.compact": 1000e-9,
                                    spans.UNSCOPED: 2000e-9})
    assert dict(managed["top_unscoped"]) == pytest.approx(
        {"while.9": 1000e-9, "copy.2": 1000e-9})


def test_idle_gaps_name_the_program_spans(managed):
    assert [n for n, _ in managed["idle_gaps"]] == \
        ["fleet.control", "fleet.dispatch"]
    assert dict(managed["idle_gaps"]) == pytest.approx(
        {"fleet.control": 4000e-9, "fleet.dispatch": 1000e-9})


def test_spans_of_a_recorded_put_and_get():
    """One put of 18 ticks and one get of 0 ticks as a v5e trace
    recorded them: the service's spans never overlap one another, so
    each request's self time is its duration less its children's, and
    the reads are the ones `tests/test_spans.py` pins (3 + 3n, 9 + n)."""
    rec = json.loads((DATA / "v5e_kv_put_get.json").read_text())["spans"]
    (put,), (get,) = rec["put"], rec["get"]
    inside = lambda iv, req: iv[0] >= req[0] and iv[1] <= req[1]
    for req, ticks, reads in ((put, 18, 57), (get, 0, 9)):
        kids = {n: [iv for iv in rec[n] if inside(iv, req)]
                for n in spans.KV_SPANS}
        assert len(kids[spans.KV_TICK]) == ticks
        assert len(kids[spans.KV_SYNC]) == reads
        assert len(kids[spans.KV_WRITE]) == 1
        flat = sorted(iv for v in kids.values() for iv in v)
        assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))
    lo = min(get[0], put[0])
    hi = max(get[1], put[1])
    got = layers.span_self_s({n: rec[n] for n in rec}, lo, hi)
    children = {n: sum(e - s for s, e in rec[n]) for n in spans.KV_SPANS}
    assert got["put"] + got["get"] == pytest.approx(
        (put[1] - put[0] + get[1] - get[0] - sum(children.values())) * 1e-9)
    for n in spans.KV_SPANS:
        assert got[n] == pytest.approx(children[n] * 1e-9)
