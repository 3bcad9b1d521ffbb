#!/usr/bin/env python3
"""Per-layer breakdown of one benchmark cell from the program's own spans,
scopes and counters (`repro.trace.spans`, `repro.compile_cache`).

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> \
        [--untraced 1] [--keep <dir>]

From the root of a checkout, on a TPU.  Set-up as in `run.py` (the
compile cache, the cell's driver built from the seed, its warm-up); then,
with `--untraced 1`, one window with the profiler off; then one window
under the profiler.  The difference of the two windows' wall time per
step is the tracing cost.  Prints one JSON line with:

  setup         the compile clock's totals at the end of set-up
                (`compile_s`, `trace_lower_s`, compiles, cache hits and
                misses) and `window_compiles`, compiles inside the windows
  windows       wall seconds, steps (epochs or ticks) and requests of each
                window, and the program's counters over it
  span_self_s   each named host span's self time inside the traced
                window: its duration less the named spans nested in it
  scope_device_s  device self time by tick phase or epoch scope,
                averaged over devices, plus `unscoped` (fleet cells; the
                op names are mapped to scopes through the compiled
                epoch's HLO text, read once after the window)
  idle_gaps     device idle time by the innermost host span it fell in,
                program spans included
  top_unscoped  the unscoped ops with the most self time
  per_step      the numbers above per epoch (fleet) or per tick (KV),
                under the names of the per-layer metrics they would feed
                (`phase_device_ms.<phase>`, `control_plane_ms_per_epoch`,
                `kv_sync_ms_per_tick`, `kv_host_reads_per_request`, ...)

`--keep <dir>` also writes `<cell>.events.json.gz` there (the window's
device op events and host spans, and each trace line's event count and
first events with their stats) and, for a fleet cell, the compiled
epoch's HLO text, `<cell>.hlo.txt.gz`: enough to redo every reduction
without the chip.

The reductions are functions of trace events, checked on synthetic and
recorded events in `tests/test_bench_layers.py`.  Nothing here is read
by `run.py` or by a metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import glob
import gzip
import json
import os
import shutil
import tempfile
from typing import Dict, Mapping, Sequence, Tuple

import harness
import trace_reduce

Interval = Tuple[float, float]
NS = 1e-9


def _clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def span_self_s(spans: Mapping[str, Sequence[Interval]], lo: float,
                hi: float) -> Dict[str, float]:
    """Seconds of each named span inside [lo, hi) ns, less the time
    covered by the named spans nested in it.  The spans come from one
    thread, so two of them are either nested or disjoint."""
    events = _clip([(n, s, e) for n, ivs in spans.items() for s, e in ivs],
                   lo, hi)
    out = {n: 0.0 for n in spans}
    for name, own in trace_reduce.self_times(events):
        out[name] += own * NS
    return out


def scope_device_s(device_ops: Sequence[Sequence[Tuple[str, float, float]]],
                   scope_of_op: Mapping[str, str], lo: float,
                   hi: float) -> Dict[str, float]:
    """Device self time inside [lo, hi) ns by scope, in seconds averaged
    over devices.  Every op lands in exactly one bucket: its scope in
    `scope_of_op`, else `unscoped`; a container (a `while` loop) keeps
    only the time its inner ops leave uncovered."""
    from repro.trace import spans
    out = {s: 0.0 for s in spans.SCOPES + (spans.UNSCOPED,)}
    for ops in device_ops:
        for name, own in trace_reduce.self_times(_clip(ops, lo, hi)):
            k = scope_of_op.get(name, spans.UNSCOPED)
            out[k] += own * NS / len(device_ops)
    return out


def op_self_s(device_ops, lo: float, hi: float) -> Dict[str, float]:
    """Device self time by op name, in seconds averaged over devices."""
    out: Dict[str, float] = {}
    for ops in device_ops:
        for name, own in trace_reduce.self_times(_clip(ops, lo, hi)):
            out[name] = out.get(name, 0.0) + own * NS / len(device_ops)
    return out


def read_profile(trace_dir: str):
    """The one `.xplane.pb` the profiler wrote under a directory."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return ProfileData.from_file(files[0])


def breakdown(profile, driver_spans: Sequence[str],
              scope_of_op: Mapping[str, str] = None) -> dict:
    """The traced window's per-layer numbers from a profile: span self
    times, idle gaps by innermost span, and, given the op-to-scope map,
    device self time by scope and the largest unscoped ops."""
    from repro.trace import spans
    names = [trace_reduce.WINDOW_SPAN, *driver_spans, *spans.HOST_SPANS]
    ops, host = trace_reduce.events(profile, names)
    summary = trace_reduce.reduce(ops, host)
    lo = min(s for s, _ in host[trace_reduce.WINDOW_SPAN])
    hi = max(e for _, e in host[trace_reduce.WINDOW_SPAN])
    out = {"window_s": summary.window_s, "busy_s": summary.busy_s,
           "span_counts": {n: len(v) for n, v in host.items()},
           "span_self_s": span_self_s(host, lo, hi),
           "idle_gaps": summary.idle_gaps,
           "device_ops": summary.device_ops}
    if scope_of_op is not None:
        out["scope_device_s"] = scope_device_s(ops, scope_of_op, lo, hi)
        by_op = op_self_s(ops, lo, hi)
        unscoped = [(n, t) for n, t in by_op.items()
                    if scope_of_op.get(n, spans.UNSCOPED) == spans.UNSCOPED]
        out["top_unscoped"] = sorted(unscoped, key=lambda kv: -kv[1])[:10]
    return out


def anatomy(profile, first: int = 3) -> dict:
    """Each plane's lines: event count and the first events with their
    stats, to see where a trace keeps what."""
    return {p.name: {ln.name: {
        "events": sum(1 for _ in ln.events),
        "first": [{"name": ev.name, "stats": [[k, str(v)]
                                              for k, v in ev.stats]}
                  for _, ev in zip(range(first), ln.events)]}
        for ln in p.lines} for p in profile.planes}


# ------------------------------------------------------------------- run
def _steps(driver) -> int:
    """Epochs (fleet) or ticks (KV) the driver has run so far."""
    if hasattr(driver, "svc"):
        return int(driver.sim.state["tick"])
    return driver.fleet.members[0].epoch


def _counters(driver) -> dict:
    if hasattr(driver, "svc"):
        return {"host_reads": driver.svc.host_reads,
                "requests": len(driver.answers)}
    return {"d2h_bytes": driver.fleet.d2h_bytes}


def _window(driver, seconds: float) -> dict:
    s0, c0 = _steps(driver), _counters(driver)
    res = driver.window(seconds)
    s1, c1 = _steps(driver), _counters(driver)
    return {"wall_s": res["counters"]["wall_s"], "steps": s1 - s0,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": res["metrics"],
            **{k: c1[k] - c0[k] for k in c1}}


def per_step(out: dict) -> dict:
    """The run's numbers per epoch (fleet) or per tick (KV), and the
    traced window's wall time per step against the untraced one's."""
    from repro.trace import spans
    win, tr = out["windows"]["traced"], out["trace"]
    n, self_s = win["steps"], tr["span_self_s"]
    ms = lambda secs: 1000.0 * secs / n if n else None
    got = {"compile_s": out["setup"]["compile_s"],
           "trace_lower_s": out["setup"]["trace_lower_s"],
           "window_compiles": out["setup"]["window_compiles"],
           "wall_ms_traced": ms(win["wall_s"])}
    if "untraced" in out["windows"]:
        u = out["windows"]["untraced"]
        got["wall_ms_untraced"] = 1000.0 * u["wall_s"] / u["steps"]
    if "host_reads" in win:
        got.update(kv_sync_ms_per_tick=ms(self_s[spans.KV_SYNC]),
                   kv_tick_dispatch_ms_per_tick=ms(self_s[spans.KV_TICK]),
                   kv_write_ms_per_tick=ms(self_s[spans.KV_WRITE]),
                   kv_host_reads_per_request=win["host_reads"] /
                   win["requests"])
    else:
        got.update({f"{name}_ms_per_epoch": ms(self_s[name])
                    for name in spans.FLEET_SPANS})
        got["control_plane_ms_per_epoch"] = ms(self_s[spans.FLEET_CONTROL])
    if "scope_device_s" in tr:
        dev = tr["scope_device_s"]
        got.update({f"phase_device_ms.{p}": ms(dev[f"tick.{p}"])
                    for p in spans.TICK_PHASES})
        got["phase_device_ms.digest_compact"] = ms(
            dev[spans.EPOCH_DIGEST] + dev[spans.EPOCH_COMPACT])
        got["unscoped_device_ms"] = ms(dev[spans.UNSCOPED])
        got["unscoped_share"] = dev[spans.UNSCOPED] / sum(dev.values())
    return got


def keep(dest: str, cell: str, profile, driver_spans, hlo) -> None:
    """Write the window's events (and the epoch's HLO text) under dest."""
    from repro.trace import spans
    os.makedirs(dest, exist_ok=True)
    names = [trace_reduce.WINDOW_SPAN, *driver_spans, *spans.HOST_SPANS]
    ops, host = trace_reduce.events(profile, names)
    lo = min(s for s, _ in host[trace_reduce.WINDOW_SPAN])
    hi = max(e for _, e in host[trace_reduce.WINDOW_SPAN])
    with gzip.open(os.path.join(dest, f"{cell}.events.json.gz"), "wt") as f:
        json.dump({"device_ops": [_clip(d, lo, hi) for d in ops],
                   "spans": host, "anatomy": anatomy(profile)}, f)
    if hlo is not None:
        with gzip.open(os.path.join(dest, f"{cell}.hlo.txt.gz"), "wt") as f:
            f.write(hlo)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--untraced", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="write the window's events (and HLO) here")
    args = ap.parse_args()
    harness.enable_compile_cache()
    bench = harness.Bench()
    entry = bench.workload(args.workload)
    cell = bench.cell(args.workload)
    device = harness.require_tpu(entry["chips"])
    harness.use_program()
    import jax
    from repro import compile_cache

    clock = compile_cache.clock()
    driver = bench.module("drivers", cell["driver"]).Driver(
        cell, bench.config(entry["config"]), bench.traffic(entry["traffic"]),
        args.seed)
    driver.warmup()
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup_s": time.perf_counter() - T_START,
           "setup": clock.totals(), "windows": {}}
    compiles0 = clock.compiles
    if args.untraced:
        out["windows"]["untraced"] = _window(driver, args.seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-layers-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            out["windows"]["traced"] = _window(driver, args.seconds)
        jax.profiler.stop_trace()
        out["setup"]["window_compiles"] = clock.compiles - compiles0
        profile = read_profile(trace_dir)
        scopes = hlo = None
        if hasattr(driver, "fleet"):
            from repro.trace import spans
            hlo = driver.fleet.epoch_hlo()
            scopes = spans.hlo_op_scopes(hlo)
        out["trace"] = breakdown(profile, driver.spans, scopes)
        out["per_step"] = per_step(out)
        if args.keep:
            keep(args.keep, args.workload, profile, driver.spans, hlo)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
