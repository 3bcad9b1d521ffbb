#!/usr/bin/env python3
"""Readings from which each check's limit is set: for every seed, one
run of a cell (set-up and a window, as `run.py` makes them), then the
comparison with the float32 reference (the sound reading) and with the
control, the reference computed in bfloat16 (the reading the limit must
reject), and, where the driver has them, the readings of faults planted
in the reference put in the program's place.  One JSON line per seed on
standard output.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Not part of a benchmark run; it runs on the chip at the cell's own
size, and `bench/tests/test_bench_control.py` runs it at a tiny size.
"""
from __future__ import annotations

import argparse
import json

import harness


def readings(bench: harness.Bench, name: str, seed: int, seconds: float,
             device: dict = None) -> dict:
    import jax.numpy as jnp
    entry = bench.workload(name)
    cell = bench.cell(name)
    if device is None:
        harness.require_tpu(entry["chips"])
    harness.use_program()
    driver = bench.module("drivers", cell["driver"]).Driver(
        cell, bench.config(entry["config"]), bench.traffic(entry["traffic"]),
        seed)
    driver.warmup()
    driver.window(seconds)
    driver.release()
    out = {"seed": seed}
    for label, fdt in (("sound", jnp.float32), ("control", jnp.bfloat16)):
        out[label] = {c.name: c.value for c in driver.check(fdt=fdt)}
    if hasattr(driver, "fault_readings"):
        out.update(driver.fault_readings())
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    harness.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(harness.Bench(), args.workload, seed,
                                  args.seconds)), flush=True)


if __name__ == "__main__":
    main()
