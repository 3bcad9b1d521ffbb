#!/usr/bin/env python
"""Fleet epoch-pipeline benchmark: digest path vs host-marshalling path.

Measures the three epoch-loop implementations on one sweep grid
(DESIGN.md §7.1):

  host        PR-1 reference, op for op: the original tick formulations
              (`step.tick(reference=True)` — scatter window adopt,
              O(L·N) commit count, A sequential apply scatters), full
              state pytree + T-stacked per-tick metrics pulled to host
              every epoch, compaction as a second dispatch, no buffer
              donation.
  device      digest pipeline: in-scan metric reduction, in-graph
              compaction, donated state — a few-KB digest per member is
              the only device→host traffic.
  device-scan the multi-epoch fast path: the whole run is ONE dispatch
              (eligible here because the grid is fixed-role/unmanaged).

Emits ``BENCH_fleet.json`` with ticks/sec, per-epoch wall time, per-epoch
device→host transfer bytes, and compile counts, and **fails** (exit 1)
when the digest pipeline regresses above fixed ceilings — per-member
per-epoch transfer bytes or total compiled programs — so CI catches
pipeline regressions (`.github/workflows/ci.yml` runs ``--smoke``).

It also measures the **sharded Multi-Raft baseline** (DESIGN.md §9): a
B-system x S-shard grid run as ONE grouped fleet — in-graph 2PC
coupling, in-graph group-digest reduction, ONE compiled dispatch per
epoch (asserted via `CountingJit`) — against the frozen sequential
`MultiRaftSim` reference, which pays B*S dispatches per epoch plus a
host round trip per shard.  The `multiraft` block in the JSON records
the dispatch-count and D2H win.

  PYTHONPATH=src python benchmarks/perf_fleet.py [--smoke] [--out PATH]

The full run (default) is the acceptance configuration: a 32-member
fleet, 5 epochs, manage off — it also asserts the ≥3X epoch-loop
speedup of the single-dispatch path over the host path — plus the
shards=4 x B=8 grouped Multi-Raft sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.configs.bwraft_kv import CONFIG
from repro.core import fleet as fleet_mod
from repro.core import multiraft
from repro.core.fleet import FleetSim
from repro.core.state import pytree_nbytes
from repro import compile_cache

# hard ceilings enforced on the digest pipeline (CI regression gates):
# per-member per-epoch device->host bytes must stay O(digest) — the
# digest is ~(T + HIST_TAIL + 2N + S + a dozen scalars) * 4 bytes
# ≈ 1.5 KB for the paper cluster (plus the per-group rows of a grouped
# fleet) — and the process must not accumulate compiled programs beyond
# one per (pipeline, static shape, group count).
D2H_CEILING_BYTES_PER_MEMBER_EPOCH = 4096
# host + device + device-scan for the sweep grid, grouped device +
# grouped device-scan for the Multi-Raft baseline (+2 slack)
COMPILE_CEILING = 7

PHIS = [0.0, 0.01, 0.02, 0.05, 0.08, 0.1, 0.15, 0.2]
WRITE_RATES = [4.0, 8.0, 16.0, 32.0]
PRELEASE = (2, 6)


def build_fleet(b: int, pipeline: str) -> FleetSim:
    phis = PHIS[:max(b // len(WRITE_RATES), 1)]
    fleet = FleetSim.from_sweep(
        CONFIG, {"phi": phis, "write_rate": WRITE_RATES},
        pipeline=pipeline, read_rate=32.0, seed=0,
        manage_resources=False, prelease=PRELEASE)
    assert fleet.shapes.B == b, fleet.shapes
    return fleet


def measure(b: int, epochs: int, pipeline: str, *,
            single_dispatch: bool) -> dict:
    """Wall time + transfer bytes for a warm (pre-compiled) run: one
    throwaway fleet pays the compile, a fresh fleet at the same static
    shape reuses the cached program (DESIGN.md §7)."""
    build_fleet(b, pipeline).run(epochs, single_dispatch=single_dispatch)
    fleet = build_fleet(b, pipeline)
    t0 = time.perf_counter()
    fleet.run(epochs, single_dispatch=single_dispatch)
    wall_s = time.perf_counter() - t0
    ticks = b * epochs * fleet.shapes.T
    return {
        "pipeline": pipeline + ("-scan" if single_dispatch else ""),
        "wall_s": wall_s,
        "epoch_wall_s": wall_s / epochs,
        "ticks_per_sec": ticks / wall_s,
        "d2h_bytes_per_epoch": fleet.d2h_bytes / epochs,
        "d2h_bytes_per_member_epoch": fleet.d2h_bytes / epochs / b,
    }


def build_multiraft_fleet(systems: int, shards: int) -> FleetSim:
    """`systems` Multi-Raft instances x `shards` shards each, every shard
    a grouped member of ONE fleet (distinct group_id per system)."""
    specs = []
    for g in range(systems):
        specs += multiraft.shard_specs(
            CONFIG, shards=shards, write_rate=8.0 + 2.0 * g,
            read_rate=32.0, cross_shard_frac=0.1, seed=g, group_id=g)
    return FleetSim(specs)


def measure_multiraft(systems: int, shards: int, epochs: int) -> dict:
    """The sharded-baseline win (DESIGN.md §9): one grouped dispatch per
    epoch for all `systems * shards` shard Rafts + in-graph 2PC + group
    digests, vs the sequential reference's one dispatch per shard per
    epoch (B*S total) with a host round trip each."""
    build_multiraft_fleet(systems, shards).run(              # warm compile
        1, single_dispatch=False)
    fleet = build_multiraft_fleet(systems, shards)
    t0 = time.perf_counter()
    fleet.run(epochs, single_dispatch=False)               # 1 dispatch/epoch
    grouped_wall = time.perf_counter() - t0
    assert fleet.compile_count == 1, \
        f"grouped Multi-Raft sweep must be ONE compiled program, " \
        f"got {fleet.compile_count}"

    def build_seq():
        return [multiraft.MultiRaftSim(
                    CONFIG, shards=shards, write_rate=8.0 + 2.0 * g,
                    read_rate=32.0, cross_shard_frac=0.1, seed=g,
                    engine="sequential")
                for g in range(systems)]
    for sim in build_seq():                                # warm compile
        sim.run_epoch()
    sims = build_seq()
    t0 = time.perf_counter()
    for _ in range(epochs):
        for sim in sims:
            sim.run_epoch()
    seq_wall = time.perf_counter() - t0

    return {
        "systems": systems, "shards": shards,
        "members": systems * shards, "epochs": epochs,
        "grouped_wall_s": grouped_wall,
        "sequential_wall_s": seq_wall,
        "speedup_grouped_vs_sequential": seq_wall / grouped_wall,
        "dispatches_per_epoch_grouped": 1,
        "dispatches_per_epoch_sequential": systems * shards,
        "d2h_bytes_per_epoch": fleet.d2h_bytes / epochs,
        "d2h_bytes_per_member_epoch":
            fleet.d2h_bytes / epochs / (systems * shards),
        "compile_count": fleet.compile_count,
    }


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grid for CI (ceiling checks only, no "
                         "speedup assertion)")
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args(argv)

    b, epochs = (8, 3) if args.smoke else (32, 5)
    print(f"=== fleet epoch pipeline: B={b}, {epochs} epochs, "
          f"manage off ===")

    runs = [measure(b, epochs, "host", single_dispatch=False),
            measure(b, epochs, "device", single_dispatch=False),
            measure(b, epochs, "device", single_dispatch=True)]
    host, device, scan = runs
    for r in runs:
        print(f"{r['pipeline']:>12}: {r['epoch_wall_s']*1e3:8.1f} ms/epoch"
              f"  {r['ticks_per_sec']:>10.0f} ticks/s"
              f"  {r['d2h_bytes_per_epoch']:>12.0f} B/epoch D2H")

    mr_systems, mr_shards = (4, 2) if args.smoke else (8, 4)
    mr = measure_multiraft(mr_systems, mr_shards, epochs)
    print(f"multiraft B={mr_systems} x S={mr_shards}: grouped "
          f"{mr['grouped_wall_s']*1e3/epochs:.1f} ms/epoch (1 dispatch) vs "
          f"sequential {mr['sequential_wall_s']*1e3/epochs:.1f} ms/epoch "
          f"({mr['dispatches_per_epoch_sequential']} dispatches): "
          f"{mr['speedup_grouped_vs_sequential']:.1f}X")

    state_bytes = pytree_nbytes(build_fleet(b, "device").state)
    result = {
        "config": {"B": b, "epochs": epochs, "T": CONFIG.period_ticks,
                   "cluster": CONFIG.name, "smoke": args.smoke},
        "runs": runs,
        "speedup_device_vs_host":
            host["epoch_wall_s"] / device["epoch_wall_s"],
        "speedup_scan_vs_host":
            host["epoch_wall_s"] / scan["epoch_wall_s"],
        "d2h_reduction_vs_host":
            host["d2h_bytes_per_epoch"] / scan["d2h_bytes_per_epoch"],
        "device_state_bytes": state_bytes,
        "multiraft": mr,
        "compile_count_total": fleet_mod.total_compile_count(),
        "ceilings": {
            "d2h_bytes_per_member_epoch":
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH,
            "compile_count_total": COMPILE_CEILING,
        },
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"speedup vs host: device {result['speedup_device_vs_host']:.1f}X"
          f", single-dispatch {result['speedup_scan_vs_host']:.1f}X; "
          f"D2H reduced {result['d2h_reduction_vs_host']:.0f}X; "
          f"{result['compile_count_total']} compiles -> {args.out}")

    failures = []
    for r in runs[1:]:
        if (r["d2h_bytes_per_member_epoch"] >
                D2H_CEILING_BYTES_PER_MEMBER_EPOCH):
            failures.append(
                f"{r['pipeline']}: {r['d2h_bytes_per_member_epoch']:.0f} "
                f"D2H bytes/member/epoch exceeds ceiling "
                f"{D2H_CEILING_BYTES_PER_MEMBER_EPOCH}")
    if mr["d2h_bytes_per_member_epoch"] > D2H_CEILING_BYTES_PER_MEMBER_EPOCH:
        failures.append(
            f"multiraft grouped: {mr['d2h_bytes_per_member_epoch']:.0f} "
            f"D2H bytes/member/epoch exceeds ceiling "
            f"{D2H_CEILING_BYTES_PER_MEMBER_EPOCH}")
    if result["compile_count_total"] > COMPILE_CEILING:
        failures.append(f"{result['compile_count_total']} compiled programs "
                        f"exceeds ceiling {COMPILE_CEILING}")
    if not args.smoke and result["speedup_scan_vs_host"] < 3.0:
        failures.append(f"single-dispatch speedup "
                        f"{result['speedup_scan_vs_host']:.2f}X < 3X")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
