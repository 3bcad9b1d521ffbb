#!/usr/bin/env python
"""Revocation-robustness benchmark: warning windows, chaos drills,
hazard-aware bidding (DESIGN.md §12).

Measures and GATES the §12 robustness contract:

  golden      W=0 + the static init-time bid must replay the committed
              pre-§12 golden trajectories (`tests/data/
              closed_loop_golden.json`) bit-identically — solo managed
              AND the fixed-role fleet.  The §12 plumbing is strictly
              additive; divergence exits 1.
  chaos       deterministic fault drills (leader kill, warned mass-site
              revocation, warning-then-reprieve) replayed through
              `core/invariants.py`: every paper safety property must
              hold, and recovery ticks are recorded per drill.
  sweep       a traces x W x bid-policy fleet must compile ONE tick
              program (W, schedules and bids are cfg_c data —
              CountingJit-asserted) under the same D2H digest ceiling
              `perf_market.py` enforces.
  retention   goodput retention vs a kill-free replay of the SAME
              price series, swept over the warning window W under the
              committed AWS trace (and the hot synthetic walk): must be
              monotonically non-decreasing in W with a net improvement
              — more warning never hurts, and reprieves/degradation
              must eventually pay.

Emits ``BENCH_faults.json``; CI runs ``--smoke`` and uploads it
(`.github/workflows/ci.yml`).

  PYTHONPATH=src python benchmarks/perf_faults.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.configs.bwraft_kv import CONFIG
from repro.core import fleet as fleet_mod
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim
from repro.market import (HazardAwareBid, MarketTrace, kill_nodes, load,
                          mass_kill, run_chaos, warning_then_reprieve)
from repro import compile_cache

GOLDEN = pathlib.Path(__file__).parent.parent / "tests" / "data" / \
    "closed_loop_golden.json"

# same digest ceiling perf_fleet.py / perf_market.py enforce (§7.1)
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096
# the retention sweep's warning grid straddles the committed AWS
# trace's revocation-run lengths (21/22/32 ticks), so the larger
# windows convert sustained signals into reprieves
W_GRID = (0, 10, 25, 40)
RETENTION_READ_RATE = 240.0      # capacity-bound: observers carry reads


def _golden_matches(g, reports, state) -> bool:
    for i, grep in enumerate(g["reports"]):
        for k, v in grep.items():
            got = getattr(reports[i], k)
            ok = (repr(float(got)) == v if isinstance(v, str)
                  else int(got) == v)
            if not ok:
                return False
    for k, leaf in g["state"].items():
        arr = np.asarray(state[k])
        if list(arr.shape) != leaf["shape"] \
                or str(arr.dtype) != leaf["dtype"] \
                or hashlib.sha256(arr.tobytes()).hexdigest() \
                != leaf["sha256"]:
            return False
    return True


def golden_gate() -> dict:
    """The W=0/static-bid gate: both committed golden recipes replayed
    through the §12-bearing code must match bit for bit."""
    golden = json.loads(GOLDEN.read_text())
    solo = BWRaftSim(CONFIG, write_rate=8.0, read_rate=32.0, phi=0.02,
                     seed=0)
    solo_ok = _golden_matches(golden["solo_managed"], solo.run(2),
                              solo.state)
    fleet = FleetSim([
        MemberSpec(cfg=CONFIG, write_rate=6.0, read_rate=24.0, seed=1,
                   manage_resources=False, prelease=(2, 6)),
        MemberSpec(cfg=CONFIG, mode="raft", write_rate=12.0,
                   read_rate=12.0, seed=2, manage_resources=False)])
    fleet.run(3)
    g = golden["fleet_fixed"]
    fleet_ok = all(
        _golden_matches({"reports": gm, "state": {}}, member_reports, {})
        for member_reports, gm in zip(fleet.reports, g["reports"])) \
        and _golden_matches({"reports": [], "state": g["state"]}, [],
                            fleet.state)
    return {"solo_managed": solo_ok, "fleet_fixed": fleet_ok,
            "bit_identical": solo_ok and fleet_ok}


def chaos_block(ticks: int = 120) -> dict:
    """The three canonical drills, market silenced (spot_bid=10.0) so
    the scripted schedule is the only fault source."""
    N = CONFIG.max_nodes
    reprieved = 4
    drills = {
        "leader_kill": (kill_nodes([0], 20, n_nodes=N, ticks=ticks), 0),
        "mass_kill_warned": (mass_kill(30, n_nodes=N, ticks=ticks,
                                       spare=(0, 1, 2), warning_ticks=3),
                             3),
        "warning_then_reprieve": (warning_then_reprieve(
            [reprieved], 20, n_nodes=N, ticks=ticks, warning_ticks=8), 8),
    }
    out = {}
    for name, (faults, w) in drills.items():
        rep = run_chaos(CONFIG, faults, warning_ticks=w, ticks=ticks,
                        seed=0, spot_bid=10.0, check=False)
        out[name] = {
            "warning_ticks": w, "first_kill_tick": rep.first_kill_tick,
            "killed": rep.killed_total,
            "recovery_ticks": rep.recovery_ticks,
            "max_leaderless_span": rep.max_leaderless_span,
            "leader_uptime": rep.leader_uptime,
            "safety_ok": rep.safety_error is None,
        }
        if name == "warning_then_reprieve":
            # the §12 reprieve contract: the signal drops one tick short
            # of landing, so THIS node must survive the whole drill
            # (other kill counts can still come from election secretary
            # drops, a §6 rule, so total `killed` is not the gate)
            out[name]["reprieved_node_survived"] = bool(
                all(snap["alive"][reprieved] for snap in rep.trace))
    return out


def sweep_block(epochs: int) -> dict:
    """traces x W x bid-policy fleet: ONE compiled tick program for the
    whole grid — windows, schedules and per-epoch bids are all cfg_c
    data at fixed shapes."""
    T = epochs * CONFIG.period_ticks
    specs = []
    for tname in ("aws-us-east", "google-evict"):
        trace = load(tname, ticks=T)
        mean = trace.fit_to(CONFIG.num_sites, T).price.mean(axis=1)
        for w in (0, 25):
            for policy in (None, HazardAwareBid(
                    mean_price=mean, window_ticks=CONFIG.period_ticks)):
                specs.append(MemberSpec(
                    cfg=CONFIG, write_rate=8.0, read_rate=32.0,
                    seed=len(specs), market="trace", trace=trace,
                    warning_ticks=w, bid_policy=policy,
                    bid_on_trace=policy is not None))
    before = fleet_mod.total_compile_count()
    FleetSim(specs).run(epochs)                        # warm compile
    compiles = fleet_mod.total_compile_count() - before
    fleet = FleetSim(specs)
    t0 = time.perf_counter()
    fleet.run(epochs)
    wall_s = time.perf_counter() - t0
    return {
        "B": len(specs), "epochs": epochs,
        "axes": {"traces": 2, "W": [0, 25], "bid_policy":
                 ["static", "hazard"]},
        "wall_s": wall_s,
        "ticks_per_sec": len(specs) * epochs * fleet.shapes.T / wall_s,
        "d2h_bytes_per_member_epoch":
            fleet.d2h_bytes / epochs / len(specs),
        "compile_count": compiles,
    }


def _retention_run(trace, warning_ticks, epochs) -> float:
    sim = BWRaftSim(CONFIG, write_rate=12.0,
                    read_rate=RETENTION_READ_RATE, seed=12,
                    manage_resources=False, market="trace", trace=trace,
                    warning_ticks=warning_ticks)
    sim.run(1)
    sim.lease_fixed(4, 8)
    return float(sum(r.goodput for r in sim.run(epochs - 1)))


def retention_block(epochs: int) -> dict:
    """Goodput retention vs W: each W member replays the SAME committed
    trace; the baseline replays the same price series with the
    revocation columns stripped (a kill-free twin).  The fig13 recipe —
    stabilize, wire (4, 8) once, never re-lease — so retention is
    purely 'how much longer did the warned complement survive'."""
    out = {}
    T = epochs * CONFIG.period_ticks
    aws = load("aws-us-east", ticks=T)
    grids = {"aws-us-east": aws}
    # the synthetic hot walk, exported so the same replay path runs it:
    # strictly harder than the committed trace (kills all epochs long)
    from repro.market import export_walk_trace
    grids["hot-walk"] = export_walk_trace(CONFIG, seed=12, epochs=epochs,
                                          spot_price_vol=2.0)
    for name, trace in grids.items():
        nokill = MarketTrace(trace.name, trace.price,
                             np.zeros_like(trace.revoked))
        base = _retention_run(nokill, 0, epochs)
        rows = {}
        for w in W_GRID:
            g = _retention_run(trace, w, epochs)
            rows[str(w)] = {"goodput": g,
                            "retention": g / max(base, 1.0)}
        out[name] = {"baseline_goodput": base, "W": rows}
    return out


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep grid for CI (gates still apply)")
    ap.add_argument("--out", default="BENCH_faults.json")
    args = ap.parse_args(argv)

    # the retention/chaos grids are pinned (they gate committed traces);
    # only the compile-sweep shrinks under --smoke
    sweep_epochs = 2 if args.smoke else 5
    epochs = 5
    print("=== revocation robustness (DESIGN.md §12) ===")

    golden = golden_gate()
    print(f"golden gate (W=0, static bid): "
          f"bit_identical={golden['bit_identical']}")

    chaos = chaos_block()
    for name, row in chaos.items():
        print(f"{name:>22}: first_kill={row['first_kill_tick']:>3} "
              f"killed={row['killed']:>2} "
              f"recovery={row['recovery_ticks']:>3} ticks "
              f"safety_ok={row['safety_ok']}")

    sweep = sweep_block(sweep_epochs)
    print(f"sweep: B={sweep['B']} {sweep['compile_count']} compile(s), "
          f"{sweep['ticks_per_sec']:.0f} ticks/s, "
          f"{sweep['d2h_bytes_per_member_epoch']:.0f} D2H B/member/epoch")

    retention = retention_block(epochs)
    for name, block in retention.items():
        r = [block["W"][str(w)]["retention"] for w in W_GRID]
        print(f"retention[{name}]: " + "  ".join(
            f"W={w}:{v:.4f}" for w, v in zip(W_GRID, r)))

    result = {
        "config": {"cluster": CONFIG.name, "epochs": epochs,
                   "sweep_epochs": sweep_epochs, "W_grid": list(W_GRID),
                   "retention_read_rate": RETENTION_READ_RATE,
                   "smoke": args.smoke},
        "golden": golden,
        "chaos": chaos,
        "sweep": sweep,
        "retention": retention,
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
    if not golden["bit_identical"]:
        failures.append("W=0/static-bid replay diverged from the golden "
                        "trajectories (§12 golden gate)")
    for name, row in chaos.items():
        if not row["safety_ok"]:
            failures.append(f"chaos drill {name} violated a safety "
                            f"property")
    if not chaos["warning_then_reprieve"]["reprieved_node_survived"]:
        failures.append("reprieve drill killed the reprieved node "
                        "(hold <= W must never land)")
    if sweep["compile_count"] != 1:
        failures.append(f"fault sweep compiled {sweep['compile_count']} "
                        f"programs (must be exactly 1)")
    if (sweep["d2h_bytes_per_member_epoch"] >
            D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
        failures.append(
            f"sweep: {sweep['d2h_bytes_per_member_epoch']:.0f} D2H "
            f"bytes/member/epoch exceeds ceiling "
            f"{D2H_CEILING_BYTES_PER_MEMBER_EPOCH}")
    aws = [retention["aws-us-east"]["W"][str(w)]["retention"]
           for w in W_GRID]
    if any(b < a for a, b in zip(aws, aws[1:])):
        failures.append(f"aws retention not monotone in W: {aws}")
    if not aws[-1] > aws[0]:
        failures.append(f"aws retention never improves with W: {aws}")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
