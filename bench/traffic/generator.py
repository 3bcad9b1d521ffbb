"""The benchmark's traffic generator: every mix under `bench/traffic/` is
a JSON file of parameters that these functions read.

Two kinds of mix:

  aggregate  the simulator's own closed-loop generator, parameterised
             by Poisson means per tick and the write-key popularity;
             `key_cdf` is the (K,) CDF the leader samples keys from.
  client     an explicit request stream for the KV service;
             `client_ops` draws it from the seed up front.

Both are fixed functions of the parameters and the seed, so the same
seed gives the same traffic, and a later PR that changes the program's
own generators (`repro.workload`) cannot move the yardstick.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

# what each kind of mix may ask for beyond its numbers: the reference
# replays only the synthetic spot-price walk, and the KV driver is one
# closed-loop client
SUPPORTED = {"aggregate": {"market": ("process",)},
             "client": {"clients": (1,)}}


def require_supported(mix: dict) -> None:
    """Refuse a mix whose parameters the benchmark cannot honour, so that
    a file never asks for something the run quietly does not do."""
    for key, allowed in SUPPORTED[mix["kind"]].items():
        if mix[key] not in allowed:
            raise ValueError(f"traffic {key}={mix[key]!r} is not supported "
                             f"(only {allowed})")


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """Inclusive CDF of P(rank r) proportional to 1/(r+1)**theta over n
    ranks, in float64, last entry exactly 1."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-theta)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf


def key_cdf(mix: dict, n_keys: int) -> np.ndarray:
    """(n_keys,) float32 CDF of the write keys of an aggregate mix."""
    keys = mix["keys"]
    if keys["dist"] == "zipfian":
        return zipf_cdf(n_keys, float(keys["theta"])).astype(np.float32)
    if keys["dist"] == "uniform":
        return (np.arange(1, n_keys + 1, dtype=np.float64) /
                n_keys).astype(np.float32)
    raise ValueError(f"unknown key distribution {keys['dist']!r}")


class KeyPopularity:
    """The `keypop` object the simulator accepts: it materializes the
    mix's key CDF for a key space (no padded tail in these cells)."""

    def __init__(self, mix: dict):
        self.mix = mix

    def materialize(self, n_keys: int, pad_keys: int = 0) -> np.ndarray:
        return np.concatenate([key_cdf(self.mix, n_keys),
                               np.ones((pad_keys,), np.float32)])


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """FNV-1a over the 8 little-endian bytes of each int64, as YCSB's
    ScrambledZipfianGenerator hashes a Zipfian rank into a record id."""
    h = np.full(x.shape, 0xCBF29CE484222325, np.uint64)
    v = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= np.uint64(0x100000001B3)
    return h


def client_ops(mix: dict, seed: int) -> List[Tuple[str, str, int]]:
    """The client's request stream: `mix["ops"]` tuples of
    (kind, key, value), kind 'put' or 'get' (value 0 for a get).  Kinds
    come in blocks of `mix["block"]` requests holding the mix's put share
    exactly, in an order drawn from the seed, so every seed asks for the
    same work in another order.  The first `warmup` puts and gets come
    first, alternating, so set-up runs both kinds before the window; the
    window continues the stream."""
    rng = np.random.default_rng(seed)
    n, records, block = int(mix["ops"]), int(mix["records"]), \
        int(mix["block"])
    keys = mix["keys"]
    assert keys["dist"] == "scrambled_zipfian", keys
    cdf = zipf_cdf(records, float(keys["theta"]))
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n), side="left"),
                       records - 1)
    ids = (fnv1a64(ranks.astype(np.int64)) % np.uint64(records)).astype(
        np.int64)
    w = mix["warmup"]
    assert w["puts"] == w["gets"], w
    puts = round(block * float(mix["put_share"]))
    is_put = np.concatenate(
        [np.arange(2 * w["puts"]) % 2 == 0] +
        [rng.permutation(np.arange(block) < puts)
         for _ in range(-(-n // block))])[:n]
    vals = rng.integers(0, int(mix["value_max"]), n)
    return [("put", f"user{int(i)}", int(v)) if p else
            ("get", f"user{int(i)}", 0)
            for p, i, v in zip(is_put, ids, vals)]


def warmup_ops(mix: dict) -> int:
    """How many leading requests of `client_ops` set-up issues."""
    w = mix["warmup"]
    return int(w["puts"]) + int(w["gets"])
