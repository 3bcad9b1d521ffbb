#!/usr/bin/env python
"""Flight-recorder benchmark: tracing overhead, drain traffic, and the
chaos-drill Perfetto artifact (DESIGN.md §14).

Measures and GATES the §14 observability contract:

  overhead    traced vs untraced epochs interleaved on ONE compiled
              program (trace_on is cfg_c data — flipping it mid-run is
              CountingJit-asserted to never recompile): the median
              traced epoch must cost <= 5% more wall time at the
              default all-classes mask.
  drain       the per-epoch ring drain is one D2H fetch of
              CAP*LANES*4 + (NCLASS+1)*4 bytes; at the default capacity
              it must stay under the same 4096 B/member/epoch digest
              ceiling perf_fleet.py enforces (§7.1) — tracing must not
              break the O(digest) transfer story.
  drill       a deterministic leader-kill chaos drill replayed with the
              recorder armed: the trace-replayed leader timeline must
              match the harness's per-tick alive-leader probe bit for
              bit (the leader track's GAPS are the measured leaderless
              spans), zero events dropped at the drill capacity, and
              the Perfetto artifact must be well-formed trace-event
              JSON.  The artifact is written next to the BENCH file
              and uploaded by CI.

Emits ``BENCH_trace.json`` (schema-checked by
`common.validate_bench_schema`); CI runs ``--smoke`` and uploads it
plus the drill artifact (`.github/workflows/ci.yml`).

  PYTHONPATH=src python benchmarks/perf_trace.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import validate_bench_schema
from repro.configs.bwraft_kv import CONFIG
from repro.core.runtime import BWRaftSim
from repro.market import kill_nodes, run_chaos
from repro.trace import ring as trace_ring
from repro import compile_cache

# same digest ceiling perf_fleet.py / perf_market.py enforce (§7.1)
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096
# the §14 overhead gate: tracing at the default mask must stay within
# 5% of the untraced tick cost (the gated-scatter emit is O(N) work
# next to the tick's O(N·L) replication ops)
OVERHEAD_CEILING_FRAC = 0.05
DRILL_TICKS = 160
DRILL_CAPACITY = 4096


def overhead_block(epochs: int, reps: int) -> dict:
    """Interleaved traced/untraced reps on one compiled epoch program.

    One sim, one compile; `set_trace` flips cfg_c between reps (the
    zero-recompile contract, asserted via the CountingJit counter), and
    the off/on reps alternate so drift (clock scaling, allocator state)
    hits both arms equally.  The gate compares medians."""
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=32.0, phi=0.02,
                    seed=0, manage_resources=False, prelease=(2, 6))
    # warm both arms on the same program
    sim.set_trace(on=False)
    sim.run(1)
    sim.set_trace(on=True)
    sim.run(1)
    compiles0 = sim._epoch_fn.cache_size()

    off_s, on_s = [], []
    for _ in range(reps):
        for traced, bucket in ((False, off_s), (True, on_s)):
            sim.set_trace(on=traced)
            t0 = time.perf_counter()
            sim.run(epochs)
            np.asarray(sim.state["tick"])        # sync
            bucket.append(time.perf_counter() - t0)
    recompiles = sim._epoch_fn.cache_size() - compiles0

    off_med, on_med = statistics.median(off_s), statistics.median(on_s)
    ticks = epochs * CONFIG.period_ticks
    return {
        "epochs_per_rep": epochs, "reps": reps,
        "off_median_s": off_med, "on_median_s": on_med,
        "off_tick_us": off_med / ticks * 1e6,
        "on_tick_us": on_med / ticks * 1e6,
        "overhead_frac": on_med / off_med - 1.0,
        "recompiles_on_toggle": recompiles,
        "events_decoded": len(sim.trace_events),
        "events_dropped": sim.events_dropped,
    }


def drain_block() -> dict:
    """Exact per-drain D2H bytes at the default ring capacity: the
    three trace leaves (`trace_ev`, `trace_pos`, `trace_emit`) by
    shape/dtype — the same accounting `state.pytree_nbytes` uses for
    the digest ceiling."""
    cap = trace_ring.DEFAULT_CAPACITY
    leaves = trace_ring.trace_leaves(cap)
    drain = sum(int(np.prod(leaves[k].shape)) * 4
                for k in ("trace_ev", "trace_pos", "trace_emit"))
    return {
        "capacity": cap, "lanes": trace_ring.LANES,
        "drain_bytes_per_member_epoch": drain,
        "metrics_registry_bytes": int(leaves["metrics_ctr"].size) * 4,
    }


def drill_block(artifact: str) -> dict:
    """Leader-kill drill with the recorder armed: safety audit + the
    trace/probe leader-timeline equivalence + the Perfetto artifact."""
    N = CONFIG.max_nodes
    faults = kill_nodes([0], 20, n_nodes=N, ticks=DRILL_TICKS,
                        name="leader-kill-traced")
    rep = run_chaos(CONFIG, faults, ticks=DRILL_TICKS, seed=0,
                    spot_bid=10.0, check=False, trace_on=True,
                    trace_capacity=DRILL_CAPACITY, trace_out=artifact)
    with open(artifact) as f:
        doc = json.load(f)
    events_ok = (isinstance(doc.get("traceEvents"), list)
                 and len(doc["traceEvents"]) > 0
                 and all({"ph", "pid", "name"} <= set(e)
                         for e in doc["traceEvents"]))
    leader_spans = [e for e in doc["traceEvents"]
                    if e.get("ph") == "X" and e.get("tid") == 9_999]
    return {
        "ticks": DRILL_TICKS, "capacity": DRILL_CAPACITY,
        "first_kill_tick": rep.first_kill_tick,
        "killed": rep.killed_total,
        "max_leaderless_span": rep.max_leaderless_span,
        "leader_uptime": rep.leader_uptime,
        "safety_ok": rep.safety_error is None,
        "events_decoded": len(rep.events),
        "events_dropped": rep.events_dropped,
        "trace_leader_match": rep.trace_leader_match,
        "perfetto_valid": bool(events_ok),
        "perfetto_events": len(doc.get("traceEvents", ())),
        "perfetto_leader_spans": len(leader_spans),
        "artifact": str(artifact),
    }


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer overhead reps for CI (gates still apply)")
    ap.add_argument("--out", default="BENCH_trace.json")
    args = ap.parse_args(argv)

    epochs = 2 if args.smoke else 4
    reps = 3 if args.smoke else 7
    artifact = str(pathlib.Path(args.out).with_name("trace_failover.json"))
    print("=== flight recorder (DESIGN.md §14) ===")

    overhead = overhead_block(epochs, reps)
    print(f"overhead: off={overhead['off_tick_us']:.1f}us/tick "
          f"on={overhead['on_tick_us']:.1f}us/tick "
          f"(+{overhead['overhead_frac'] * 100:.2f}%), "
          f"{overhead['recompiles_on_toggle']} recompile(s) on toggle, "
          f"{overhead['events_decoded']} events decoded")

    drain = drain_block()
    print(f"drain: CAP={drain['capacity']} -> "
          f"{drain['drain_bytes_per_member_epoch']} B/member/epoch "
          f"(ceiling {D2H_CEILING_BYTES_PER_MEMBER_EPOCH})")

    drill = drill_block(artifact)
    print(f"drill: killed={drill['killed']} "
          f"max_leaderless={drill['max_leaderless_span']} "
          f"leader_match={drill['trace_leader_match']} "
          f"events={drill['events_decoded']} "
          f"perfetto_valid={drill['perfetto_valid']} -> {artifact}")

    result = {
        "config": {"cluster": CONFIG.name, "epochs_per_rep": epochs,
                   "reps": reps, "drill_ticks": DRILL_TICKS,
                   "drill_capacity": DRILL_CAPACITY,
                   "smoke": args.smoke},
        "overhead": overhead,
        "drain": drain,
        "drill": drill,
        "ceilings": {
            "tick_overhead_frac": OVERHEAD_CEILING_FRAC,
            "drain_d2h_bytes_per_member_epoch":
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH,
            "recompiles_on_toggle": 0,
            "events_dropped_total": 0,
        },
    }
    schema_problems = validate_bench_schema(result, name=args.out)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"-> {args.out}")

    failures = list(schema_problems)
    if overhead["overhead_frac"] > OVERHEAD_CEILING_FRAC:
        failures.append(
            f"tracing overhead {overhead['overhead_frac'] * 100:.2f}% "
            f"exceeds the {OVERHEAD_CEILING_FRAC * 100:.0f}% ceiling")
    if overhead["recompiles_on_toggle"] != 0:
        failures.append(
            f"trace toggles recompiled {overhead['recompiles_on_toggle']} "
            f"program(s) (trace_on/trace_mask must be cfg_c data)")
    if (drain["drain_bytes_per_member_epoch"] >
            D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
        failures.append(
            f"ring drain {drain['drain_bytes_per_member_epoch']} B exceeds "
            f"the {D2H_CEILING_BYTES_PER_MEMBER_EPOCH} B digest ceiling")
    if not drill["safety_ok"]:
        failures.append("traced chaos drill violated a safety property")
    if drill["trace_leader_match"] is not True:
        failures.append("trace-replayed leader timeline diverged from the "
                        "chaos harness's per-tick leader probe")
    if not drill["perfetto_valid"]:
        failures.append("Perfetto artifact is not well-formed trace-event "
                        "JSON")
    dropped = dict(overhead["events_dropped"])
    for k, v in drill["events_dropped"].items():
        dropped[k] = dropped.get(k, 0) + v
    if any(dropped.values()):
        failures.append(f"events dropped at benchmark capacities: "
                        f"{dropped}")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
