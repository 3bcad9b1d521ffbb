#!/usr/bin/env python
"""A 32-cluster parameter sweep in ONE compiled program.

    PYTHONPATH=src python examples/sweep_fleet.py [--backend pallas]

Sweeps the paper cluster over an 8 x 4 grid of spot kill rates (phi) and
write rates — 32 independent BW-Raft clusters — with `FleetSim`.  All 32
clusters advance together inside a single jitted, vmapped tick-scan: the
sweep grid enters as batched jit *arguments*, so the whole figure-shaped
experiment costs exactly ONE compilation of the epoch function
(DESIGN.md §7).  The script asserts that via `FleetSim.compile_count`.

Epochs run on the device-resident digest pipeline (DESIGN.md §7.1): the
state pytree never leaves the device — per epoch only a few-KB digest per
cluster is fetched (printed below; compare with the device state size).
`benchmarks/perf_fleet.py` quantifies the speedup vs the PR-1
host-marshalling path and records it in BENCH_fleet.json.

`--backend pallas` runs the same sweep through the Pallas kernel layer
(raft_tick + leader fan-out + grouped digest reduction + anti-entropy
sync; DESIGN.md §8; interpret mode on CPU) — trajectories are
bit-identical, only execution differs; `benchmarks/perf_tick.py` is the
measured comparison.  `--backend auto` (the library default) resolves
per platform: pallas on TPU, xla everywhere else — the resolved choice
is printed and asserted below.
"""
import argparse
import itertools
import time

from repro.configs.bwraft_kv import CONFIG
from repro.core.fleet import FleetSim
from repro.core.runtime import BWRaftSim
from repro.core.state import pytree_nbytes
from repro.kernels import BACKENDS, resolve_backend
from repro import compile_cache

PHIS = [0.0, 0.01, 0.02, 0.05, 0.08, 0.1, 0.15, 0.2]
WRITE_RATES = [4.0, 8.0, 16.0, 32.0]
EPOCHS = 3


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=BACKENDS, default="auto",
                    help="tick hot-op implementation (DESIGN.md §8); "
                         "'auto' resolves to pallas on TPU, xla elsewhere")
    args = ap.parse_args()
    resolved = resolve_backend(args.backend)
    print(f"=== BW-Raft fleet sweep: 8 phis x 4 write rates = 32 clusters "
          f"(backend={args.backend} -> {resolved}) ===")
    fleet = FleetSim.from_sweep(
        CONFIG, {"phi": PHIS, "write_rate": WRITE_RATES},
        read_rate=32.0, seed=0, backend=args.backend)
    assert fleet.shapes.B == 32, fleet.shapes
    assert fleet.backend == resolved, (fleet.backend, resolved)

    t0 = time.perf_counter()
    reports = fleet.run(EPOCHS)
    batched_s = time.perf_counter() - t0

    assert fleet.compile_count == 1, (
        f"expected exactly one jit compilation of the batched epoch "
        f"function, got {fleet.compile_count}")
    print(f"ran {fleet.shapes.B} clusters x {EPOCHS} epochs "
          f"({fleet.shapes.B * EPOCHS * fleet.shapes.T} cluster-ticks) in "
          f"{batched_s:.1f}s with {fleet.compile_count} compile")
    print(f"device->host per epoch: {fleet.d2h_bytes // EPOCHS} B of "
          f"digests vs {pytree_nbytes(fleet.state)} B of device-resident "
          f"state (never fetched; DESIGN.md §7.1)")

    print(f"\n{'phi':>5} | " + " | ".join(
        f"w={int(w):>2} goodput" for w in WRITE_RATES))
    grid = itertools.product(PHIS, WRITE_RATES)
    by_cell = {cell: reps[-1] for cell, reps in zip(grid, reports)}
    for phi in PHIS:
        cells = [f"{by_cell[(phi, w)].goodput:>12.0f}"
                 for w in WRITE_RATES]
        print(f"{phi:>5.2f} | " + " | ".join(cells))

    # one sequential point for scale: same cluster, same epochs, 1/32 of
    # the work — every additional point would pay this again
    t0 = time.perf_counter()
    BWRaftSim(CONFIG, write_rate=8.0, read_rate=32.0, phi=0.05,
              seed=0).run(EPOCHS)
    solo_s = time.perf_counter() - t0
    print(f"\nsequential single cluster: {solo_s:.1f}s -> 32 points "
          f"~{32 * solo_s:.0f}s sequential vs {batched_s:.1f}s batched "
          f"({32 * solo_s / max(batched_s, 1e-9):.1f}x)")
    print("OK")


if __name__ == "__main__":
    main()
