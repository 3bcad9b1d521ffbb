#!/usr/bin/env python
"""Spot-market subsystem benchmark: trace replay vs the synthetic walk.

Measures and GATES the §10 market contract (DESIGN.md §10):

  replay      the synthetic walk exported as a trace
              (`market/synthetic.export_walk_trace`) and replayed through
              the trace path must reproduce the process path
              **bit-identically** — states and reports — with the
              control plane managing.  Divergence exits 1 (the market
              analogue of `perf_tick.py`'s equivalence gate).
  sweep       a B-member fleet with a DIFFERENT (S, T) trace per member
              must compile ONE program and run `run(E)` as ONE dispatch
              (CountingJit-asserted via `fleet.total_compile_count`),
              with per-member-epoch device→host bytes under the same
              digest ceiling `perf_fleet.py` enforces; trace-replay tick
              overhead vs the synthetic walk is recorded (and gated at
              OVERHEAD_CEILING on the full run).
  comparison  the paper's Fig. 8 story on a real market: BW-Raft vs
              original Raft vs Multi-Raft cost/goodput under a committed
              sample trace, next to the synthetic-walk numbers.
  calibration `market.calibrate` fit quality: RevocationPredictor
              alpha/MAE against the Google-eviction sample,
              moment-matched walk parameters against the AWS sample.

Emits ``BENCH_market.json``; CI runs ``--smoke`` and uploads it
(`.github/workflows/ci.yml`).

  PYTHONPATH=src python benchmarks/perf_market.py [--smoke] [--out PATH]
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
from repro.core.runtime import BWRaftSim
from repro.market import (calibrate_predictor, export_walk_trace, fit_walk,
                          load)
from benchmarks.common import run_systems
from repro import compile_cache

# trace replay swaps one (S,) RNG-normal draw for one (S,) dynamic-slice
# gather per tick — it must stay within this factor of the walk
OVERHEAD_CEILING = 2.0
# same digest ceiling perf_fleet.py enforces (DESIGN.md §7.1)
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096

_REPORT_FIELDS = ("reads_arrived", "writes_arrived", "reads_served",
                  "writes_committed", "killed", "n_secretaries",
                  "n_observers", "leader_changes", "no_leader_ticks",
                  "cost")


def replay_gate(epochs: int) -> dict:
    """§10 replay invariant on the paper cluster, manager ON: process
    run vs exported-walk replay must match bit for bit."""
    kw = dict(write_rate=8.0, read_rate=32.0, phi=0.02, seed=0)
    process = BWRaftSim(CONFIG, **kw)
    process_reports = process.run(epochs)
    trace = export_walk_trace(CONFIG, seed=0, epochs=epochs)
    replay = BWRaftSim(CONFIG, **kw, market="trace", trace=trace)
    replay_reports = replay.run(epochs)

    state_ok = all(np.array_equal(np.asarray(process.state[k]),
                                  np.asarray(replay.state[k]))
                   for k in process.state)
    reports_ok = all(
        getattr(a, f) == getattr(b, f)
        for a, b in zip(process_reports, replay_reports)
        for f in _REPORT_FIELDS)
    return {"epochs": epochs, "cluster": CONFIG.name,
            "managed": True, "phi": 0.02,
            "bit_identical": bool(state_ok and reports_ok),
            "state_identical": bool(state_ok),
            "reports_identical": bool(reports_ok)}


def _sweep_fleet(b: int, epochs: int, market: str) -> FleetSim:
    specs = []
    for i in range(b):
        trace = (export_walk_trace(CONFIG, seed=i, epochs=epochs)
                 if market == "trace" else None)
        specs.append(MemberSpec(
            cfg=CONFIG, write_rate=4.0 + 2.0 * (i % 4), read_rate=32.0,
            seed=i, manage_resources=False, prelease=(2, 6),
            market=market, trace=trace))
    return FleetSim(specs)


def measure_sweep(b: int, epochs: int, market: str) -> dict:
    """Warm-compile then time a B-member single-dispatch run; report
    wall time, ticks/sec, D2H bytes, and the compile delta this market
    mode cost (must be exactly 1 program for the whole run)."""
    before = fleet_mod.total_compile_count()
    _sweep_fleet(b, epochs, market).run(epochs)              # warm compile
    compiles = fleet_mod.total_compile_count() - before
    fleet = _sweep_fleet(b, epochs, market)
    assert fleet.single_dispatch_eligible
    t0 = time.perf_counter()
    fleet.run(epochs)
    wall_s = time.perf_counter() - t0
    return {
        "market": market, "B": b, "epochs": epochs,
        "wall_s": wall_s,
        "epoch_wall_s": wall_s / epochs,
        "ticks_per_sec": b * epochs * fleet.shapes.T / wall_s,
        "d2h_bytes_per_member_epoch": fleet.d2h_bytes / epochs / b,
        "dispatches_per_run": 1,
        "compile_count": compiles,
    }


def _report_row(rep) -> dict:
    return {"goodput": rep.goodput, "cost": rep.cost,
            "cost_per_kop": 1000 * rep.cost / max(rep.goodput, 1),
            "write_lat_p95": rep.write_lat_p95}


def market_comparison(epochs: int, trace_name: str) -> dict:
    """Fig. 8 on a real market: the three systems under the committed
    sample trace vs under the synthetic walk (same seeds/loads)."""
    kw = dict(write_rate=16.0, read_rate=48.0, epochs=epochs, shards=2)
    trace = load(trace_name, ticks=epochs * CONFIG.period_ticks)
    out = {}
    for label, mkw in (("synthetic", dict(market="process")),
                       (trace_name, dict(market="trace", trace=trace))):
        bw, og, mr = run_systems(CONFIG, **kw, **mkw)
        out[label] = {"bwraft": _report_row(bw), "original": _report_row(og),
                      "multiraft": _report_row(mr),
                      "bwraft_cost_saving_vs_multiraft":
                          1.0 - bw.cost / max(mr.cost, 1e-9)}
    return out


def calibration_block() -> dict:
    predictor, rep = calibrate_predictor(
        load("google-evict", ticks=1200), CONFIG.period_ticks)
    walk = fit_walk(load("aws-us-east", ticks=1200))
    return {
        "predictor": {"trace": rep.trace, "alpha": rep.alpha,
                      "mae": rep.mae, "one_step_mse": rep.one_step_mse,
                      "empirical": rep.empirical.tolist(),
                      "fitted": rep.fitted.tolist()},
        "walk": {"trace": walk.trace, "vol": walk.vol,
                 "vol_per_site": walk.vol_per_site.tolist(),
                 "mean": walk.mean.tolist(),
                 "reversion_r2": walk.reversion_r2},
    }


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid for CI (no overhead-ceiling gate)")
    ap.add_argument("--out", default="BENCH_market.json")
    args = ap.parse_args(argv)

    b, epochs = (4, 2) if args.smoke else (16, 5)
    print(f"=== spot-market subsystem: B={b}, {epochs} epochs ===")

    replay = replay_gate(epochs)
    print(f"replay invariant (managed, phi=0.02): "
          f"bit_identical={replay['bit_identical']}")

    process = measure_sweep(b, epochs, "process")
    trace = measure_sweep(b, epochs, "trace")
    overhead = trace["epoch_wall_s"] / process["epoch_wall_s"]
    for r in (process, trace):
        print(f"{r['market']:>9}: {r['epoch_wall_s']*1e3:8.1f} ms/epoch"
              f"  {r['ticks_per_sec']:>10.0f} ticks/s"
              f"  {r['compile_count']} compile(s), "
              f"{r['dispatches_per_run']} dispatch/run")
    print(f"trace-replay tick overhead vs synthetic walk: {overhead:.2f}X")

    comparison = market_comparison(epochs, "aws-us-east")
    for label, row in comparison.items():
        print(f"{label:>12}: bwraft ${row['bwraft']['cost']:.4f} vs "
              f"multiraft ${row['multiraft']['cost']:.4f} "
              f"({100*row['bwraft_cost_saving_vs_multiraft']:.1f}% saving)")

    calibration = calibration_block()
    print(f"calibration: predictor alpha="
          f"{calibration['predictor']['alpha']} "
          f"mae={calibration['predictor']['mae']:.4f}; "
          f"walk vol fit {calibration['walk']['vol']:.3f}")

    result = {
        "config": {"B": b, "epochs": epochs, "T": CONFIG.period_ticks,
                   "cluster": CONFIG.name, "smoke": args.smoke},
        "replay": replay,
        "sweep": {"process": process, "trace": trace,
                  "trace_overhead_vs_process": overhead},
        "comparison": comparison,
        "calibration": calibration,
        "ceilings": {
            "trace_overhead_vs_process": OVERHEAD_CEILING,
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
    if not replay["bit_identical"]:
        failures.append("trace replay diverged from the synthetic walk "
                        "(§10 replay invariant)")
    for r in (process, trace):
        if r["compile_count"] != 1:
            failures.append(
                f"{r['market']} sweep compiled {r['compile_count']} "
                f"programs (must be exactly 1)")
        if (r["d2h_bytes_per_member_epoch"] >
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
            failures.append(
                f"{r['market']}: {r['d2h_bytes_per_member_epoch']:.0f} "
                f"D2H bytes/member/epoch exceeds ceiling "
                f"{D2H_CEILING_BYTES_PER_MEMBER_EPOCH}")
    if not args.smoke and overhead > OVERHEAD_CEILING:
        failures.append(f"trace-replay overhead {overhead:.2f}X exceeds "
                        f"ceiling {OVERHEAD_CEILING}X")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
