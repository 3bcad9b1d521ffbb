"""What every cell shares: finding its files by name, the device check,
the compile cache, the system under test's import path, and the
comparisons that decide `correct`.

Files are found by name only:

  BENCHMARK.json             the cells, metrics and bounds (at the root)
  bench/cells/<cell>.json    a cell's driver and its parameters
  bench/configs/<cfg>.json   a deployment, as it is run
  bench/traffic/<mix>.json   a traffic mix (read by traffic/generator.py)
  bench/drivers/<kind>.py    a driver: builds, warms up, runs, checks
  bench/metrics/<name>.py    a per-layer metric's reader
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
from typing import Dict, List

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


class Bench:
    """The benchmark's files under a checkout root, found by name."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.dir = self.root / "bench"

    def benchmark(self) -> dict:
        return load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return load_json(self.dir / "cells" / f"{name}.json")

    def config(self, name: str) -> dict:
        return load_json(self.dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def module(self, kind: str, name: str):
        """`bench/<kind>/<name>.py` as a module of its own."""
        path = self.dir / kind / f"{name}.py"
        tag = "".join(ch if ch.isalnum() else "_" for ch in name)
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{tag}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> dict:
        """The cell's entry in BENCHMARK.json, checked against its file."""
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                c = self.cell(name)
                for k in ("config", "traffic"):
                    if c[k] != w[k]:
                        raise ValueError(
                            f"cell {name}: {k} is {c[k]!r} in its file but "
                            f"{w[k]!r} in BENCHMARK.json")
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_of(self, name: str, section: str) -> List[dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.benchmark()[section]
                if "workloads" not in m or name in m["workloads"]]


# ------------------------------------------------------------- the device
def require_tpu(chips: int) -> dict:
    """The device record of the result line; exits non-zero unless JAX
    sees a TPU with at least `chips` chips.  Never falls back."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {d.platform!r} device(s) "
                       f"({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no statistics: the CPU of the benchmark's own tests)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def use_program() -> None:
    """Put the system under test (`src/`) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (`repro.compile_cache.enable`: `JAX_COMPILATION_CACHE_DIR`, else a
    fixed path in the checkout), with every program cached however small
    or quick to compile, so a cell's second run compiles nothing."""
    use_program()
    import jax
    from repro import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


SHARED_NODE_MODEL = ("work_capacity", "msg_budget", "entries_per_msg",
                     "max_ship", "max_apply")


def require_node_model(cfg: dict, static: dict, cfg_c: dict) -> None:
    """Refuse a deployment whose `node_model` the program does not run:
    the program fixes its node model in code, so a file can state it but
    not change it, and the reference reads it from the file."""
    nm = cfg["node_model"]
    means = np.asarray([s["spot_price_mean"]
                        for s in cfg["cluster"]["sites"]], np.float64)
    have = {k: static[k] for k in SHARED_NODE_MODEL}
    have.update(ticks_per_hour=np.float32(cfg_c["ticks_per_hour"]),
                network_cost_coef=np.float32(cfg_c["network_cost_coef"]))
    want = {k: nm[k] for k in SHARED_NODE_MODEL}
    want.update(ticks_per_hour=np.float32(nm["ticks_per_hour"]),
                network_cost_coef=np.float32(nm["network_cost_coef"]))
    bid = np.asarray(cfg_c["spot_bid"])[:means.size]
    wrong = [k for k in want if have[k] != want[k]]
    if not np.array_equal(bid, (means * nm["bid_over_mean"]).astype(
            np.float32)):
        wrong.append("bid_over_mean")
    if set(nm) != set(want) | {"bid_over_mean"} or wrong:
        raise ValueError(f"{cfg['name']}: the program does not run the "
                         f"node model the file states ({wrong or sorted(nm)})")


def cluster_config(cfg: dict):
    """The program's `ClusterConfig` for a deployment file."""
    from repro.core.cluster_config import ClusterConfig, SiteConfig
    c = dict(cfg["cluster"])
    sites = tuple(SiteConfig(**s) for s in c.pop("sites"))
    return ClusterConfig(name=cfg["name"], sites=sites, **c)


def member_seeds(seed: int, n: int) -> List[int]:
    """`n` member seeds derived from the run's seed (31-bit, so every
    seed is a valid PRNG key and numpy seed)."""
    state = np.random.SeedSequence(seed).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


# ------------------------------------------------------------ comparisons
@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only if every check has value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (not math.isnan(self.value)) and self.value <= self.limit


def rel_gap(got, ref) -> float:
    """Largest |got - ref| / max(|ref|, tiny) over two float arrays."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - ref) /
                        np.maximum(np.abs(ref), 1e-30)))


def int_leaves(state: Dict) -> List[str]:
    """Names of a state's integer and boolean leaves."""
    import jax.numpy as jnp
    return [k for k, v in state.items()
            if not jnp.issubdtype(np.asarray(v).dtype, jnp.floating)]


def int_mismatches(got: Dict, ref: Dict, keys) -> int:
    """Elements that differ between two sets of integer leaves."""
    n = 0
    for k in keys:
        a, b = np.asarray(got[k]), np.asarray(ref[k])
        n += a.size if a.shape != b.shape else int(np.sum(a != b))
    return n


def worst(*gaps: float) -> float:
    """The largest gap; NaN, a gap that could not be read, wins."""
    return float("nan") if any(math.isnan(g) for g in gaps) else max(gaps)


def percentile(values, q: float) -> float:
    """numpy's linear-interpolation percentile, where +inf entries (failed
    requests) count as misses beyond every finite value."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    pos = (v.size - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
