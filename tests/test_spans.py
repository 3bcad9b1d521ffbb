"""Profiler spans, scopes and counters of the program (DESIGN.md §14):
the compiled fleet epoch and the KV service's put program carry every
phase scope in their ops' `op_name` metadata, an op maps to its
innermost scope, the service counts its blocking device-to-host reads,
its program dispatches and the ticks stepped inside them exactly,
the service and the fleet put their spans into a profiler trace nested
under the caller's, and the compile clock counts a fresh compile."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.core.cluster_config import ClusterConfig, SiteConfig
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim
from repro.kvstore.service import BWKVService
from repro.trace import spans


def _tiny_cluster():
    sites = tuple(
        SiteConfig(f"spans-s{i}", followers=f, rtt_intra=1,
                   rtt_inter=6 + 2 * i, on_demand_price=0.0416,
                   spot_price_mean=0.0125)
        for i, f in enumerate((2, 2, 1)))
    return ClusterConfig(name="spans", sites=sites, max_log=128,
                         key_space=256, max_secretaries=4,
                         max_observers=8, period_ticks=20)


@pytest.fixture(scope="module")
def fleet():
    cfg = _tiny_cluster()
    return FleetSim([MemberSpec(cfg=cfg, write_rate=4.0, read_rate=8.0,
                                seed=s, manage_resources=s == 0,
                                n_observers=8, ae_interval=2)
                     for s in range(2)])


@pytest.fixture(scope="module")
def svc():
    sim = BWRaftSim(_tiny_cluster(), write_rate=0.0, read_rate=0.0, seed=3,
                    manage_resources=False, n_observers=8, ae_interval=2)
    s = BWKVService(sim)
    s._step(60)
    return s


def _op_name_scopes(hlo_text):
    return {spans.scope_of(m)
            for m in re.findall(r'op_name="([^"]*)"', hlo_text)} - {None}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(epoch)/vmap()/while/body/closed_call/tick.leader/mul",
     "tick.leader"),
    ("jit(epoch)/vmap(epoch.compact)/rev", "epoch.compact"),
    ("jit(f)/vmap(jvp(tick.anti_entropy))/add", "tick.anti_entropy"),
    ("jit(epoch)/epoch.digest/while/body/tick.cost/sin", "tick.cost"),
    ("jit(epoch)/vmap()/while/body/add", None),
    ("jit(f)/tick.leaderboard/mul", None),
    ("jit(f)/mytick.spot/mul", None),
    ("", None),
])
def test_an_op_counts_under_its_innermost_scope(op_name, scope):
    assert spans.scope_of(op_name) == scope


def test_hlo_instructions_map_to_scopes():
    text = "\n".join([
        "%fused_computation (p: s32[4]) -> s32[4] {",
        '  ROOT %mul.1 = s32[4]{0} multiply(%p, %p), '
        'metadata={op_name="jit(e)/while/body/tick.leader/mul"}',
        "}",
        "ENTRY %main (a: s32[4]) -> s32[4] {",
        '  %fusion.7 = s32[4]{0} fusion(%a), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(e)/while/body/'
        'closed_call/tick.leader/mul" source_file="x.py" source_line=3}',
        '  %rev.2 = s32[4]{0} reverse(%fusion.7), dimensions={0}, '
        'metadata={op_name="jit(e)/vmap(epoch.compact)/rev"}',
        "  %copy.3 = s32[4]{0} copy(%rev.2)",
        '  ROOT %add.4 = s32[4]{0} add(%copy.3, %copy.3), '
        'metadata={op_name="jit(e)/add"}',
        "}"])
    assert spans.hlo_op_scopes(text) == {
        "mul.1": "tick.leader", "fusion.7": "tick.leader",
        "rev.2": "epoch.compact", "copy.3": spans.UNSCOPED,
        "add.4": spans.UNSCOPED}


def test_fleet_epoch_carries_every_scope(fleet):
    hlo = fleet.epoch_hlo()
    assert _op_name_scopes(hlo) == set(spans.SCOPES)
    by_op = spans.hlo_op_scopes(hlo)
    assert set(by_op.values()) <= set(spans.SCOPES) | {spans.UNSCOPED}
    assert any("fusion" in k and v.startswith("tick.")
               for k, v in by_op.items())


def _top_level_ops(hlo_text):
    """(computation, instruction, shape, opcode) of every instruction
    outside fused computations: the ops a device trace names."""
    comps, fused, cur = {}, set(), None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) \(", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        instr = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", line)
        if instr and cur is not None:
            rest = instr.group(2)
            op = re.search(r"\s([a-z][\w\-]*)\(", rest)   # after the shape
            cur.append((instr.group(1), rest[:op.start()], op.group(1)))
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    return [(c, *op) for c, ops in comps.items() if c not in fused
            for op in ops]


def test_scopes_add_metadata_and_no_operation(fleet, monkeypatch):
    """The epoch compiled with and without the scopes has the same
    instructions outside fused computations, here under the same names,
    so a trace of either maps through the other's HLO (on a TPU v5e, 11
    of 2,922 broadcasts and squeezes are numbered apart: PERF.md)."""
    import contextlib
    import jax.numpy as jnp
    from repro.core.fleet import _vmapped_epoch

    def compiled():
        fn = jax.jit(_vmapped_epoch(fleet.shapes, fleet._shared,
                                    fleet.backend))
        rngs = jnp.stack([m.rng for m in fleet.members])
        return fn.lower(fleet.state, rngs, fleet._bstatic,
                        fleet._cfg_c).compile().as_text()
    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert _op_name_scopes(scoped) and not _op_name_scopes(bare)
    assert _top_level_ops(scoped) == _top_level_ops(bare)
    assert len(_top_level_ops(bare)) > 100


def test_epoch_hlo_reads_this_codes_scopes(monkeypatch):
    """A program compiled before the scopes existed (here: with them
    switched off), as a cache may serve it, does not hide them from
    `epoch_hlo`, which traces afresh."""
    import contextlib
    cfg = _tiny_cluster()
    fleet3 = FleetSim([MemberSpec(cfg=cfg, write_rate=4.0, read_rate=8.0,
                                  seed=s, n_observers=8, ae_interval=2)
                       for s in range(3)])
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        fleet3.run_epoch()
    assert _op_name_scopes(fleet3.epoch_hlo()) == set(spans.SCOPES)


def test_kv_tick_carries_every_tick_scope(svc):
    """The put program steps the cluster inside its loop: every tick
    phase keeps its scope there."""
    sim = svc.sim
    hlo = svc._put.lower(sim.state, sim.cfg_c, sim.rng, 0, 1,
                         svc.timeout).compile().as_text()
    assert _op_name_scopes(hlo) == set(spans.TICK_SCOPES)


def test_kv_service_counts_its_blocking_reads(svc):
    """A put and a fenced get each run one device program and read its
    answer once, however many ticks they step; `device_ticks` counts the
    ticks stepped inside the programs, which are the requests' latency
    ticks here (the leader is elected, so nothing else is waited for)."""
    r0, d0, t0 = svc.host_reads, svc.dispatches, svc.device_ticks
    put = svc.put("spans", 11)
    assert put.latency_ticks > 0
    assert (svc.host_reads - r0, svc.dispatches - d0) == (1, 1)
    assert svc.device_ticks - t0 == put.latency_ticks
    r1, d1 = svc.host_reads, svc.dispatches
    value, _ = svc.get("spans")
    assert value == 11
    assert (svc.host_reads - r1, svc.dispatches - d1) == (1, 1)
    assert svc.device_ticks - t0 == put.latency_ticks + \
        svc.read_latencies[-1]


def _host_spans(trace_dir, names):
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in out:
                        out[ev.name].append((ev.start_ns, ev.end_ns))
    return out


def _inside(inner, outer):
    return all(any(s >= os_ and e <= oe for os_, oe in outer)
               for s, e in inner)


def test_service_and_fleet_spans_land_in_a_trace(svc, fleet, tmp_path):
    reads0, dispatches0 = svc.host_reads, svc.dispatches
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("put"):
            svc.put("traced", 5)
        with jax.profiler.TraceAnnotation("get"):
            svc.get("traced")
        with jax.profiler.TraceAnnotation("epoch"):
            fleet.run_epoch()
    finally:
        jax.profiler.stop_trace()
    got = _host_spans(str(tmp_path), ("put", "get", "epoch") +
                      spans.HOST_SPANS)
    assert len(got[spans.KV_SYNC]) == svc.host_reads - reads0 == 2
    assert len(got[spans.KV_TICK]) == svc.dispatches - dispatches0 == 2
    assert got[spans.KV_WRITE] == []              # both ran on the device
    kv = got[spans.KV_SYNC] + got[spans.KV_TICK] + got[spans.KV_WRITE]
    assert _inside(kv, got["put"] + got["get"])
    for name in (spans.FLEET_DISPATCH, spans.FLEET_FETCH,
                 spans.FLEET_CONTROL, spans.FLEET_WRITEBACK):
        assert len(got[name]) == 1, name
        assert _inside(got[name], got["epoch"])
    assert got[spans.FLEET_DRAIN] == []           # the recorder is off


def test_compile_clock_counts_a_fresh_compile():
    clock = compile_cache.clock()
    assert compile_cache.clock() is clock
    before = clock.totals()
    x = np.arange(7, dtype=np.float32)
    jax.jit(lambda v: v * 3.0 + 0.25)(x).block_until_ready()
    after = clock.totals()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]
    assert after["trace_lower_s"] > before["trace_lower_s"]
    assert clock.seconds == pytest.approx(
        after["compile_s"] + after["trace_lower_s"])
