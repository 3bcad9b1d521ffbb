"""Helpers of the benchmark's own tests: the benchmark's modules on the
import path, and a copy of the benchmark at a tiny size (a 3-site
cluster, 20-tick epochs, 256 keys) in a temporary checkout, so each
driver runs through the same functions as on the chip."""
import importlib.util
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CLUSTER = {"max_log": 128, "key_space": 256, "period_ticks": 20,
                "max_secretaries": 4, "max_observers": 8}
TINY_CELLS = {"members": 3, "check_block": 2}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# a cell that the benchmark does not measure yet, added to the tiny copy
# as data alone, so the fixed-role scan driver runs as it would on the chip
SCAN_CELL = {"config": "bwraft-paper-4region",
             "traffic": "ycsbA.w16r16.zipf099", "driver": "fleet_scan",
             "why": "fixed roles on the multi-epoch single-dispatch scan",
             "members": 3, "roles": [2, 6], "epochs_per_dispatch": 2,
             "check_block": 2, "trace_seconds": None,
             "limits": {"state_mismatch": 0, "price_gap": 0.001,
                        "cost_gap": 0.01}}
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]] + ["paper4r.scan"]


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_copy(dest: pathlib.Path) -> pathlib.Path:
    """The benchmark copied under `dest`, every deployment shrunk to a
    size the CPU runs in seconds; returns the new checkout root."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["cluster"]["sites"] = cfg["cluster"]["sites"][:3]
        cfg["cluster"].update(TINY_CLUSTER)
        if cfg["digest_tier"]["n_observers"]:
            cfg["digest_tier"]["n_observers"] = 16
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "cells").glob("*.json"):
        cell = json.loads(path.read_text())
        cell.update({k: v for k, v in TINY_CELLS.items() if k in cell})
        path.write_text(json.dumps(cell))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        if "records" in mix:
            mix.update(records=256, ops=64)
        path.write_text(json.dumps(mix))
    add_cell(dest, "paper4r.scan", SCAN_CELL, like="paper4r.managed")
    return dest


def add_cell(root: pathlib.Path, name: str, cell: dict, like: str) -> None:
    """A new cell as data alone: its file, its BENCHMARK.json entry, and
    every metric that cell `like` reports."""
    (root / "bench" / "cells" / f"{name}.json").write_text(json.dumps(cell))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append(
        {"name": name, "config": cell["config"], "traffic": cell["traffic"],
         "chips": 1, "why": cell["why"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
