#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell, its deployment, its traffic mix,
its driver and its per-layer metric readers are found by name (see
`harness.py`); nothing here depends on which cell runs.  Order:

  1. refuse anything but a TPU with the chips the cell asks for;
  2. set-up: build the system from the seed, warm up every shape the
     window uses (compiling, or reading the compile cache) -> `setup_s`;
  3. the window: the driver's timed loop for `--seconds` (with
     `--trace 1`, under the profiler and for at most the cell's
     `trace_seconds`);
  4. read the chip's peak memory, free the system's state, and compare
     what the window produced with the plain reference (`correct`);
  5. print the checks on standard error and the JSON result as the last
     line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import math
import shutil
import sys
import tempfile

import harness
import trace_reduce


def run_cell(bench: harness.Bench, name: str, seed: int, seconds: float,
             trace: bool, device: dict = None) -> dict:
    """One run of cell `name`; returns the result object.  `device` is
    the device record when the caller has already checked the chip."""
    entry = bench.workload(name)
    cell = bench.cell(name)
    cfg = bench.config(entry["config"])
    mix = bench.traffic(entry["traffic"])
    if device is None:
        device = harness.require_tpu(entry["chips"])
    harness.use_program()
    import jax

    driver = bench.module("drivers", cell["driver"]).Driver(
        cell, cfg, mix, seed)
    driver.warmup()
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            seconds = min(seconds, cell.get("trace_seconds") or seconds)
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            res = driver.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        device = dict(device, memory_peak_bytes=harness.memory_peak_bytes())
        driver.release()
        summary = (trace_reduce.read_dir(trace_dir, driver.spans)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = driver.check()

    out = {"correct": all(c.ok for c in checks),
           "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        ctx = {"trace": summary, "counters": res["counters"]}
        metrics = {}
        for m in bench.metrics_of(name, "per_layer"):
            value = bench.module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=summary.busy_s,
                      window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench.metrics_of(name, "end_to_end")}
    out.update(metrics=metrics, device=device,
               checks={c.name: {"value": c.value, "limit": c.limit}
                       for c in checks})
    return out


def finite(x):
    """JSON has no inf or NaN: an infinite number (a tail of failed
    requests, a gap with nothing to divide by) prints as the largest
    float, an unreadable one as null."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None if math.isnan(x) else math.copysign(sys.float_info.max,
                                                        x)
    return x


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    harness.enable_compile_cache()
    out = run_cell(harness.Bench(), args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r}  limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(out), allow_nan=False), flush=True)


if __name__ == "__main__":
    main()
