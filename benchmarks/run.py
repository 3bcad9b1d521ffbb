"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Consensus benchmarks run inline
(1 CPU device) and, by default, drive their sweep grids through the
batched fleet simulator (`core/fleet.FleetSim`): every (system, load)
point in a figure is one member of a single vmapped program, so a grid
costs one jit compile instead of one per point (DESIGN.md §7).  The
roofline benchmark needs 512 virtual CPU devices and runs as a child
process with `JAX_PLATFORMS=cpu`: it only compiles, and the parent may
hold the accelerator (its results are also cached under results/).

  PYTHONPATH=src python -m benchmarks.run [--full] [--sequential]
                                          [--with-roofline] [--only NAME]

--sequential falls back to the pre-fleet one-BWRaftSim-per-point path
(same seeds; identical results at equal static shapes) — useful for
A/B-ing the batched path or isolating a fleet regression.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

MODULES = [
    "fig6_snapshots", "fig7_scaleout", "fig8_overall", "fig9_cdf",
    "fig10_roles", "fig11_ycsb", "fig12_alpha", "fig13_failure",
    "fig14_sites",
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow)")
    ap.add_argument("--sequential", action="store_true",
                    help="one BWRaftSim per grid point instead of one "
                         "batched FleetSim per figure")
    ap.add_argument("--with-roofline", action="store_true",
                    help="also run one roofline cell as a subprocess")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from benchmarks import common
    common.USE_FLEET = not args.sequential

    rows = []
    mods = [m for m in MODULES if not args.only or args.only in m]
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            out = mod.run(quick=not args.full)
        except Exception as e:  # pragma: no cover
            print(f"# {name} FAILED: {e}", file=sys.stderr)
            raise
        dt = (time.perf_counter() - t0) * 1e6
        rows.extend(out)
        rows.append((f"{name}.wall", dt / max(len(out), 1), "us_per_row"))

    if common.USE_FLEET:
        from repro.core import fleet
        rows.append(("fleet.compiled_epoch_programs",
                     float(fleet.total_compile_count()), "count"))

    if args.with_roofline:
        cmd = [sys.executable, "-m", "benchmarks.roofline",
               "--arch", "llama3.2-1b", "--shape", "decode_32k"]
        t0 = time.perf_counter()
        # the parent has imported jax through the figure modules and may
        # hold the chip; the child compiles for virtual CPU devices only
        subprocess.run(cmd, check=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
        rows.append(("roofline.llama_decode.wall",
                     (time.perf_counter() - t0) * 1e6, "us"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
