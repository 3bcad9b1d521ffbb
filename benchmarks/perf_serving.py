#!/usr/bin/env python
"""Open-loop SLO-goodput serving benchmark (DESIGN.md §11).

Measures and GATES the serving surface:

  gate        a `ConstantRate` open-loop plan at the closed-loop scalar
              rates must reproduce the closed-loop run **bit-identically**
              — states and reports — because the per-tick rate lookup
              selects the same Poisson intensity and the key draw is
              untouched.  Divergence exits 1 (the serving analogue of
              `perf_market.py`'s replay gate).
  sweep       a B-member open-loop fleet — diurnal curves, flash-crowd
              bursts, Zipfian keys, a DIFFERENT plan per member — must
              compile ONE program and run `run(E)` as ONE dispatch
              (CountingJit-asserted via `fleet.total_compile_count`),
              with per-member-epoch device→host bytes under the same
              digest ceiling `perf_fleet.py` enforces.  The full grid
              simulates ~1M requests per epoch in that one dispatch;
              arrived/served request volumes are recorded.
  comparison  the headline: BW-Raft vs original Raft vs Multi-Raft under
              the SAME open-loop plan (shards at `shard_workload`-divided
              intensity), scored by **goodput under a p95 deadline** —
              requests served within `P95_DEADLINE_TICKS`, read straight
              off the unit-bin read/write digest histograms
              (`runtime.goodput_under_deadline`; the Multi-Raft write
              side deduplicates cross-shard prepares by 1/(1+chi), the
              same arithmetic as its report counts).

Emits ``BENCH_serving.json``; CI runs ``--smoke`` and uploads it
(`.github/workflows/ci.yml`).

  PYTHONPATH=src python benchmarks/perf_serving.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.configs.bwraft_kv import CONFIG
from repro.core import fleet as fleet_mod
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim, goodput_under_deadline
from repro.workload import (ConstantRate, DiurnalRate, FlashCrowd, OpenLoop,
                            ZipfianKeys)
from benchmarks.common import system_specs, tick_ms
from repro import compile_cache

# the serving SLO: a request is good if it completes within this many
# ticks (1 tick = 10 ms — a 300 ms deadline, see `common.tick_ms`)
P95_DEADLINE_TICKS = 30
# same digest ceiling perf_fleet.py / perf_market.py enforce (§7.1)
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096

_REPORT_FIELDS = ("reads_arrived", "writes_arrived", "reads_served",
                  "writes_committed", "killed", "n_secretaries",
                  "n_observers", "leader_changes", "no_leader_ticks",
                  "cost")


def closed_loop_gate(epochs: int) -> dict:
    """§11 coexistence invariant on the paper cluster, manager ON: a
    flat open-loop plan at the closed-loop rates must match the
    closed-loop run bit for bit (same Poisson intensity per tick, key
    draw untouched)."""
    kw = dict(write_rate=8.0, read_rate=32.0, phi=0.02, seed=0)
    closed = BWRaftSim(CONFIG, **kw)
    closed_reports = closed.run(epochs)
    plan = OpenLoop(write=ConstantRate(8.0), read=ConstantRate(32.0),
                    ticks=CONFIG.period_ticks)
    opened = BWRaftSim(CONFIG, **kw, arrivals=plan)
    open_reports = opened.run(epochs)

    state_ok = all(np.array_equal(np.asarray(closed.state[k]),
                                  np.asarray(opened.state[k]))
                   for k in closed.state)
    reports_ok = all(
        getattr(a, f) == getattr(b, f)
        for a, b in zip(closed_reports, open_reports)
        for f in _REPORT_FIELDS)
    return {"epochs": epochs, "cluster": CONFIG.name,
            "managed": True, "phi": 0.02,
            "bit_identical": bool(state_ok and reports_ok),
            "state_identical": bool(state_ok),
            "reports_identical": bool(reports_ok)}


def _member_plan(i: int, read_rate: float, write_rate: float) -> OpenLoop:
    """A distinct diurnal + flash-crowd plan per member: phase-shifted
    day/night curve, burst windows offset per member."""
    writes = DiurnalRate(write_rate, amplitude=0.5,
                         phase=0.3 * i)
    reads = FlashCrowd(DiurnalRate(read_rate, amplitude=0.5,
                                   phase=0.3 * i),
                       mult=4.0, every_ticks=50, burst_ticks=5,
                       offset=7 * i)
    return OpenLoop(write=writes, read=reads,
                    ticks=2 * CONFIG.period_ticks)


def _sweep_fleet(b: int, read_rate: float, write_rate: float) -> FleetSim:
    specs = [MemberSpec(
        cfg=CONFIG, write_rate=write_rate, read_rate=read_rate,
        seed=i, manage_resources=False, prelease=(2, 6),
        arrivals=_member_plan(i, read_rate, write_rate),
        keypop=ZipfianKeys(1.1)) for i in range(b)]
    return FleetSim(specs)


def measure_sweep(b: int, epochs: int, read_rate: float,
                  write_rate: float) -> dict:
    """Warm-compile then time a B-member open-loop single-dispatch run;
    report wall time, request volumes, D2H bytes, and the compile delta
    (must be exactly 1 program for the whole run)."""
    before = fleet_mod.total_compile_count()
    _sweep_fleet(b, read_rate, write_rate).run(epochs)    # warm compile
    compiles = fleet_mod.total_compile_count() - before
    fleet = _sweep_fleet(b, read_rate, write_rate)
    assert fleet.single_dispatch_eligible
    t0 = time.perf_counter()
    reports = fleet.run(epochs)
    wall_s = time.perf_counter() - t0
    arrived = sum(r.reads_arrived + r.writes_arrived
                  for m in reports for r in m)
    served = sum(r.reads_served + r.writes_committed
                 for m in reports for r in m)
    return {
        "B": b, "epochs": epochs,
        "read_rate": read_rate, "write_rate": write_rate,
        "wall_s": wall_s,
        "epoch_wall_s": wall_s / epochs,
        "ticks_per_sec": b * epochs * fleet.shapes.T / wall_s,
        "requests_arrived_per_epoch": arrived / epochs,
        "requests_served_per_epoch": served / epochs,
        "requests_per_sec": arrived / wall_s,
        "d2h_bytes_per_member_epoch": fleet.d2h_bytes / epochs / b,
        "dispatches_per_run": 1,
        "compile_count": compiles,
    }


def _slo_row(read_hist, write_hist, rep, deadline: int,
             write_dedup: float = 1.0) -> dict:
    """Score one system's epoch from its digest histograms: goodput
    under the deadline (reads + deduplicated writes) next to the
    arrival volume and the read/write tails."""
    good_r = goodput_under_deadline(read_hist, deadline)
    good_w = int(goodput_under_deadline(write_hist, deadline) / write_dedup)
    arrived = int(rep.reads_arrived + rep.writes_arrived)
    return {
        "goodput_under_deadline": good_r + good_w,
        "good_reads": good_r, "good_writes": good_w,
        "requests_arrived": arrived,
        "slo_attainment": (good_r + good_w) / max(arrived, 1),
        "read_lat_p95": rep.read_lat_p95,
        "read_lat_p99": rep.read_lat_p99,
        "write_lat_p95": rep.write_lat_p95,
        "cost": rep.cost,
    }


def serving_comparison(epochs: int, *, write_rate: float = 16.0,
                       read_rate: float = 48.0, shards: int = 2,
                       deadline: int = P95_DEADLINE_TICKS) -> dict:
    """BW-Raft vs original Raft vs Multi-Raft under the same open-loop
    plan, scored by goodput under the p95 deadline — one batched fleet,
    histograms straight off the last epoch's digest."""
    plan = OpenLoop(write=DiurnalRate(write_rate, amplitude=0.5),
                    read=FlashCrowd(DiurnalRate(read_rate, amplitude=0.5),
                                    mult=4.0),
                    ticks=2 * CONFIG.period_ticks)
    chi = 0.1
    specs = system_specs(CONFIG, write_rate=write_rate,
                         read_rate=read_rate, shards=shards, group_id=0,
                         arrivals=plan, keypop=ZipfianKeys(1.1))
    fleet = FleetSim(specs)
    fleet.run(epochs)
    dg, gdg = fleet.last_digest, fleet.last_group_digest
    bw = fleet.members[0].reports[-1]
    og = fleet.members[1].reports[-1]
    mr = fleet.group_reports[0][-1]
    return {
        "deadline_ticks": deadline,
        "deadline_ms": tick_ms(deadline),
        "plan": {"write": f"diurnal({write_rate})",
                 "read": f"flashcrowd(diurnal({read_rate}))",
                 "keys": "zipfian(1.1)",
                 "ticks": 2 * CONFIG.period_ticks},
        "bwraft": _slo_row(dg["read_lat_hist"][0], dg["write_lat_hist"][0],
                           bw, deadline),
        "original": _slo_row(dg["read_lat_hist"][1],
                             dg["write_lat_hist"][1], og, deadline),
        "multiraft": _slo_row(gdg["read_lat_hist"][0],
                              gdg["write_lat_hist"][0], mr, deadline,
                              write_dedup=1 + chi),
    }


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid for CI")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)

    if args.smoke:
        b, epochs, read_rate, write_rate = 4, 2, 48.0, 8.0
    else:
        b, epochs, read_rate, write_rate = 32, 5, 300.0, 20.0
    print(f"=== open-loop serving surface: B={b}, {epochs} epochs ===")

    gate = closed_loop_gate(epochs)
    print(f"closed-loop gate (flat plan, managed, phi=0.02): "
          f"bit_identical={gate['bit_identical']}")

    sweep = measure_sweep(b, epochs, read_rate, write_rate)
    print(f"open-loop sweep: {sweep['epoch_wall_s']*1e3:8.1f} ms/epoch"
          f"  {sweep['requests_arrived_per_epoch']:>12.0f} reqs/epoch"
          f"  {sweep['compile_count']} compile(s), "
          f"{sweep['dispatches_per_run']} dispatch/run")

    comparison = serving_comparison(epochs)
    for label in ("bwraft", "original", "multiraft"):
        row = comparison[label]
        print(f"{label:>10}: goodput@{comparison['deadline_ms']:.0f}ms "
              f"{row['goodput_under_deadline']:>7d} "
              f"({100*row['slo_attainment']:.1f}% of arrivals)  "
              f"read p99 {row['read_lat_p99']:.0f} ticks  "
              f"cost ${row['cost']:.4f}")

    result = {
        "config": {"B": b, "epochs": epochs, "T": CONFIG.period_ticks,
                   "read_rate": read_rate, "write_rate": write_rate,
                   "cluster": CONFIG.name, "smoke": args.smoke},
        "gate": gate,
        "sweep": sweep,
        "comparison": comparison,
        "ceilings": {
            "d2h_bytes_per_member_epoch":
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH,
            "compile_count_per_sweep": 1,
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"-> {args.out}")

    failures = []
    if not gate["bit_identical"]:
        failures.append("flat open-loop plan diverged from the "
                        "closed-loop run (§11 coexistence invariant)")
    if sweep["compile_count"] != 1:
        failures.append(f"open-loop sweep compiled "
                        f"{sweep['compile_count']} programs "
                        f"(must be exactly 1)")
    if (sweep["d2h_bytes_per_member_epoch"] >
            D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
        failures.append(
            f"{sweep['d2h_bytes_per_member_epoch']:.0f} D2H "
            f"bytes/member/epoch exceeds ceiling "
            f"{D2H_CEILING_BYTES_PER_MEMBER_EPOCH}")
    for label in ("bwraft", "original", "multiraft"):
        if comparison[label]["goodput_under_deadline"] <= 0:
            failures.append(f"{label}: zero goodput under the "
                            f"{P95_DEADLINE_TICKS}-tick deadline")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
