#!/usr/bin/env python
"""Digest-tier observer scale-out benchmark (DESIGN.md §13).

Measures and GATES the paper's 50X-node claim — observers are massive,
cheap, and near-stateless, so BW-Raft scales to ~50X the nodes of
original Raft:

  invariance  a run with a digest tier attached (O > 0) must leave every
              dense voter-core leaf — logs, terms, roles, commit/apply
              indices, the rolling applied digest, the KV image, RNG-fed
              kill/price streams — bit-identical to the O = 0 run at the
              same seed.  The tier only ever *adds* digest-shaped state
              and redistributes reads; divergence exits 1.
  curve       per-tick wall cost and read-staleness percentiles vs.
              observer count, N_obs from 0 into the thousands.  Every
              point is an unmanaged single-member fleet whose `run(E)`
              collapses into ONE compiled dispatch (CountingJit-asserted,
              §7.1); per-tick cost must stay SUBLINEAR in N_obs (the
              tier is one fused `(O,)` gather/where pass, not O copies
              of the dense tick).
  sweep       `n_observers` is a sweep axis like phi or write_rate: a
              mixed-width fleet (0 … N_max observers, padded to one
              static shape) must compile ONE program, run as ONE
              dispatch, and stay under the §7.1 digest D2H ceiling.
  staleness   every digest-tier read is served within the configured
              bound: the per-member `obs_stale_p99` read off the device
              staleness histogram must be <= `staleness_bound`.

The headline gate: N_obs >= 50 x the voter count of the paper cluster,
in one compiled dispatch.

Emits ``BENCH_observers.json``; CI runs ``--smoke`` and uploads it
(`.github/workflows/ci.yml`).

  PYTHONPATH=src python benchmarks/perf_observers.py [--smoke] [--out P]
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
from repro import compile_cache

# same digest ceiling perf_fleet.py / perf_serving.py enforce (§7.1)
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096
STALENESS_BOUND = 12
AE_INTERVAL = 4

# the dense voter core: every leaf that must stay bit-identical when a
# digest tier rides along (DESIGN.md §13 equivalence invariant).  The
# tier is allowed to move ONLY read serving (read_queue and the counters
# and histograms downstream of it) and cost (digest observers lease spot
# capacity); everything else — consensus, logs, applied state, RNG
# streams — is core.
_NON_CORE = ("read_queue", "reads_served", "read_lat_hist",
             "read_lat_sum", "read_lat_max", "cost_accrued")


def _is_core_leaf(name: str) -> bool:
    return (not name.startswith("dobs_") and not name.startswith("obs_")
            and name not in _NON_CORE)


def voter_core_invariance(epochs: int, n_obs: int) -> dict:
    """O = 0 vs O = `n_obs` at the same seed: every core leaf equal."""
    kw = dict(write_rate=8.0, read_rate=48.0, phi=0.05, seed=7,
              manage_resources=False, prelease=(2, 8))
    base = BWRaftSim(CONFIG, **kw)
    base.run(epochs)
    tier = BWRaftSim(CONFIG, **kw, n_observers=n_obs,
                     staleness_bound=STALENESS_BOUND,
                     ae_interval=AE_INTERVAL)
    reports = tier.run(epochs)
    diverged = [k for k in base.state if _is_core_leaf(k)
                and not np.array_equal(np.asarray(base.state[k]),
                                       np.asarray(tier.state[k]))]
    rep = reports[-1]
    return {"epochs": epochs, "n_observers": n_obs,
            "core_leaves_checked": sum(_is_core_leaf(k)
                                       for k in base.state),
            "diverged_leaves": diverged,
            "core_bit_identical": not diverged,
            "obs_reads_served": rep.obs_reads_served,
            "tier_served_reads": rep.obs_reads_served > 0}


def _point_fleet(n_obs: int, seed: int = 0) -> FleetSim:
    spec = MemberSpec(cfg=CONFIG, mode="bwraft", write_rate=8.0,
                      read_rate=64.0, phi=0.02, seed=seed,
                      manage_resources=False, prelease=(2, 8),
                      n_observers=n_obs,
                      staleness_bound=STALENESS_BOUND,
                      ae_interval=AE_INTERVAL)
    return FleetSim([spec])


def measure_point(n_obs: int, epochs: int) -> dict:
    """One scale-out point: warm-compile, then time `run(epochs)` as one
    dispatch; report per-tick wall cost and the staleness tail."""
    before = fleet_mod.total_compile_count()
    _point_fleet(n_obs).run(epochs)                       # warm compile
    compiles = fleet_mod.total_compile_count() - before
    fleet = _point_fleet(n_obs)
    assert fleet.single_dispatch_eligible
    t0 = time.perf_counter()
    reports = fleet.run(epochs)
    wall_s = time.perf_counter() - t0
    rep = reports[0][-1]
    ticks = epochs * fleet.shapes.T
    return {
        "n_obs": n_obs, "epochs": epochs,
        "wall_s": wall_s,
        "tick_wall_us": wall_s / ticks * 1e6,
        "obs_reads_served": rep.obs_reads_served,
        "obs_rerouted": rep.obs_rerouted,
        "obs_stale_p95": rep.obs_stale_p95,
        "obs_stale_p99": rep.obs_stale_p99,
        "n_obs_digest_alive": rep.n_obs_digest,
        "reads_served": rep.reads_served,
        "compile_count": compiles,
        "dispatches_per_run": 1,
        "d2h_bytes_per_member_epoch": fleet.d2h_bytes / epochs,
    }


def measure_mixed_sweep(widths, epochs: int) -> dict:
    """`n_observers` as a sweep axis: one fleet, one program, one
    dispatch for members of every width (padded to max(widths))."""
    def build():
        return FleetSim([
            MemberSpec(cfg=CONFIG, mode="bwraft", write_rate=8.0,
                       read_rate=64.0, phi=0.02, seed=3 + i,
                       manage_resources=False, prelease=(2, 8),
                       n_observers=o, staleness_bound=STALENESS_BOUND,
                       ae_interval=AE_INTERVAL)
            for i, o in enumerate(widths)])
    before = fleet_mod.total_compile_count()
    build().run(epochs)                                   # warm compile
    compiles = fleet_mod.total_compile_count() - before
    fleet = build()
    assert fleet.single_dispatch_eligible
    t0 = time.perf_counter()
    reports = fleet.run(epochs)
    wall_s = time.perf_counter() - t0
    rows = [{"n_obs": o,
             "obs_reads_served": m[-1].obs_reads_served,
             "obs_stale_p99": m[-1].obs_stale_p99,
             "n_obs_digest_alive": m[-1].n_obs_digest}
            for o, m in zip(widths, reports)]
    return {
        "widths": list(widths), "epochs": epochs,
        "wall_s": wall_s,
        "compile_count": compiles,
        "dispatches_per_run": 1,
        "d2h_bytes_per_member_epoch":
            fleet.d2h_bytes / epochs / len(widths),
        "members": rows,
    }


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid for CI")
    ap.add_argument("--out", default="BENCH_observers.json")
    args = ap.parse_args(argv)

    voters = sum(1 + s.followers for s in CONFIG.sites)
    target = 50 * voters
    if args.smoke:
        epochs, widths = 2, [0, 56, target]
    else:
        epochs, widths = 3, [0, 56, target, 896, 1792, 3584]
    n_max = max(widths)
    print(f"=== digest-tier scale-out: V={voters} voters, "
          f"N_obs up to {n_max} ({n_max / voters:.0f}x), "
          f"{epochs} epochs ===")

    inv = voter_core_invariance(epochs, target)
    print(f"voter-core invariance (O=0 vs O={target}): "
          f"bit_identical={inv['core_bit_identical']} "
          f"({inv['core_leaves_checked']} leaves)"
          + (f"  DIVERGED: {inv['diverged_leaves']}"
             if inv["diverged_leaves"] else ""))

    curve = [measure_point(o, epochs) for o in widths]
    for row in curve:
        print(f"  N_obs {row['n_obs']:>5d}: "
              f"{row['tick_wall_us']:>8.1f} us/tick  "
              f"obs reads {row['obs_reads_served']:>6d}  "
              f"stale p99 {row['obs_stale_p99']:>5.1f}  "
              f"({row['compile_count']} compile, 1 dispatch)")

    lo = next(r for r in curve if r["n_obs"] > 0)
    hi = curve[-1]
    n_ratio = hi["n_obs"] / lo["n_obs"]
    wall_ratio = hi["tick_wall_us"] / lo["tick_wall_us"]
    print(f"sublinearity: N_obs x{n_ratio:.1f} -> "
          f"tick cost x{wall_ratio:.2f}")

    sweep = measure_mixed_sweep(widths, epochs)
    print(f"mixed-width sweep ({len(widths)} members): "
          f"{sweep['compile_count']} compile(s), 1 dispatch, "
          f"{sweep['d2h_bytes_per_member_epoch']:.0f} D2H B/member/epoch")

    result = {
        "config": {"cluster": CONFIG.name, "voters": voters,
                   "T": CONFIG.period_ticks, "epochs": epochs,
                   "staleness_bound": STALENESS_BOUND,
                   "ae_interval": AE_INTERVAL,
                   "target_50x": target, "n_obs_max": n_max,
                   "smoke": args.smoke},
        "invariance": inv,
        "curve": curve,
        "sublinearity": {"n_ratio": n_ratio, "wall_ratio": wall_ratio},
        "mixed_sweep": sweep,
        "ceilings": {
            "d2h_bytes_per_member_epoch":
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH,
            "compile_count_per_point": 1,
            "staleness_p99": STALENESS_BOUND,
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"-> {args.out}")

    failures = []
    if not inv["core_bit_identical"]:
        failures.append(f"digest tier perturbed the dense voter core: "
                        f"{inv['diverged_leaves']} (§13 equivalence)")
    if not inv["tier_served_reads"]:
        failures.append("digest tier served zero reads in the "
                        "invariance run")
    if n_max < target:
        failures.append(f"N_obs max {n_max} below the 50X target "
                        f"{target}")
    if wall_ratio >= n_ratio:
        failures.append(f"per-tick cost superlinear in N_obs: "
                        f"x{wall_ratio:.2f} wall for x{n_ratio:.1f} "
                        f"observers")
    for row in curve:
        if row["compile_count"] != 1:
            failures.append(f"N_obs={row['n_obs']} compiled "
                            f"{row['compile_count']} programs "
                            f"(must be exactly 1)")
        if (row["d2h_bytes_per_member_epoch"] >
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
            failures.append(f"N_obs={row['n_obs']}: "
                            f"{row['d2h_bytes_per_member_epoch']:.0f} "
                            f"D2H bytes/member/epoch over ceiling")
        if row["n_obs"] > 0 and not (
                row["obs_stale_p99"] <= STALENESS_BOUND):
            failures.append(f"N_obs={row['n_obs']}: staleness p99 "
                            f"{row['obs_stale_p99']} over bound "
                            f"{STALENESS_BOUND}")
    if sweep["compile_count"] != 1:
        failures.append(f"mixed-width sweep compiled "
                        f"{sweep['compile_count']} programs "
                        f"(must be exactly 1)")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
