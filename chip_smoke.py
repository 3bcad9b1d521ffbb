#!/usr/bin/env python3
"""Smoke test of the BW-Raft fleet and KV service on one TPU.

    python3 chip_smoke.py [--seed N]

Drives the main path once through the entry points a user calls, at the
paper cluster's own widths (4 sites, 7 voters, 16 secretary and 64
observer slots: N = 87; log window L = 4,096; T = 100 ticks per epoch)
with the key space raised to 100,000 keys, the scale of etcd's put
benchmark.  Phases, all in this one process:

  1. device   — exits non-zero unless JAX's first device is a TPU;
  2. fleet    — `FleetSim.from_sweep` over the 8 phi x 4 write-rate grid
                of `examples/sweep_fleet.py` (32 clusters) on the XLA
                backend: 3 managed epochs, then 3 fixed-role epochs in
                one dispatch (`lease_fixed`, as in fig12/fig13);
  3. pallas   — the same sweep on the compiled Pallas kernels; every
                integer digest and state leaf must equal phase 2;
  4. multiraft — `MultiRaftSim(shards=4)` on the grouped engine, pallas
                against XLA, integer leaves exactly equal;
  5. kv       — `BWKVService` over `BWRaftSim` with a 550-slot digest
                tier: every acknowledged put reads back through the
                fenced `get` and `get_stale`; phi = 1 then revokes every
                spot node and put/get keep working through the voters.

Any failure exits non-zero.  The last line of standard output is one
JSON object naming the device.  Each phase is a function of its sizes,
so `tests/test_chip_smoke.py` runs the same control flow on the CPU at a
tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PHIS = [0.0, 0.01, 0.02, 0.05, 0.08, 0.1, 0.15, 0.2]
WRITE_RATES = [4.0, 8.0, 16.0, 32.0]
READ_RATE = 32.0
KEY_SPACE = 100_000
EPOCHS = 3
FIXED_ROLES = (2, 6)
MR_SHARDS, MR_EPOCHS = 4, 2
KV_OBSERVERS, KV_KEYS = 550, 200


# ------------------------------------------------------------------ device
def check_device() -> dict:
    """Print what JAX sees; exit non-zero unless it is a TPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"devices: {devs}")
    print(f"platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def require(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def _timed(clock, fn, sync):
    """(wall seconds, compile seconds) of `fn()`, the wall clock stopped
    only once `sync()` is ready on the device."""
    import jax
    c0 = clock.seconds
    t0 = time.perf_counter()
    fn()
    jax.block_until_ready(sync())
    return time.perf_counter() - t0, clock.seconds - c0


def differing_int_leaves(a, b):
    """Names of integer/bool leaves of two same-structure state pytrees
    that differ (compared on the device, one bool per leaf fetched)."""
    import jax.numpy as jnp
    return [k for k in a
            if not jnp.issubdtype(a[k].dtype, jnp.floating)
            and not bool(jnp.array_equal(a[k], b[k]))]


def compare_digests(ref: dict, got: dict, label: str,
                    float_rtol: float = 0.0) -> int:
    """Integer leaves must be equal exactly, and float leaves too unless
    `float_rtol` is given: only the grouped Multi-Raft reduction gets
    one, because XLA may order its float sums differently in the pallas
    and the XLA program.  Each differing float leaf is printed with its
    largest absolute difference; returns how many float leaves differ."""
    import numpy as np
    require(ref.keys() == got.keys(),
            f"{label}: leaf sets differ: {ref.keys() ^ got.keys()}")
    n_float = 0
    for k in sorted(ref):
        if isinstance(ref[k], dict):
            n_float += compare_digests(ref[k], got[k], f"{label}.{k}",
                                       float_rtol)
            continue
        x, y = np.asarray(ref[k]), np.asarray(got[k])
        if not np.issubdtype(x.dtype, np.floating):
            require(np.array_equal(x, y),
                    f"{label}: integer leaf {k} differs between backends")
        elif not np.array_equal(x, y, equal_nan=True):
            n_float += 1
            diff = float(np.nanmax(np.abs(x - y)))
            print(f"  float leaf {label}.{k} differs: max |diff| = "
                  f"{diff!r}")
            require(np.allclose(x, y, rtol=float_rtol, atol=0.0,
                                equal_nan=True),
                    f"{label}: float leaf {k} off by {diff!r}")
    return n_float


# ------------------------------------------------------------------ fleet
def run_fleet(cfg, phis, write_rates, *, backend, epochs, seed, clock):
    """Phase 2/3 body: the managed sweep epoch by epoch, then the
    fixed-role sweep through the single-dispatch scan.  Returns per-path
    numbers, the per-epoch digests and the final states."""
    from repro.core.fleet import FleetSim, total_compile_count
    from repro.core.state import pytree_nbytes

    out = {}
    programs0 = total_compile_count()
    axes = {"phi": list(phis), "write_rate": list(write_rates)}
    fleet = FleetSim.from_sweep(cfg, axes, read_rate=READ_RATE, seed=seed,
                                backend=backend)
    B, T = fleet.shapes.B, fleet.shapes.T
    digests, walls, comps = [], [], []
    for _ in range(epochs):
        w, c = _timed(clock, fleet.run_epoch, lambda: fleet.state)
        walls.append(w)
        comps.append(c)
        digests.append(fleet.last_digest)
    warm = sum(walls[1:])
    out["managed"] = dict(
        B=B, epochs=epochs, compile_s=sum(comps), warm_s=warm,
        cluster_ticks_per_s=B * T * (epochs - 1) / warm if warm else None,
        compile_count=total_compile_count() - programs0,
        d2h_bytes_per_epoch=fleet.d2h_bytes // epochs,
        state_bytes=pytree_nbytes(fleet.state),
        digests=digests, state=fleet.state,
        reports=[[r.goodput for r in m] for m in fleet.reports])

    fixed = FleetSim.from_sweep(cfg, axes, read_rate=READ_RATE, seed=seed,
                                backend=backend, manage_resources=False)
    require(fixed.single_dispatch_eligible, "fixed-role fleet must scan")
    programs0 = total_compile_count()
    fixed.run(1)                                  # leadership stabilizes
    fixed.lease_fixed(*FIXED_ROLES)
    d0 = fixed.d2h_bytes
    _, c1 = _timed(clock, lambda: fixed.run(epochs), lambda: fixed.state)
    d1 = fixed.d2h_bytes
    last1 = fixed.last_digest
    w2, c2 = _timed(clock, lambda: fixed.run(epochs), lambda: fixed.state)
    out["fixed"] = dict(
        B=B, epochs=epochs, compile_s=c1 + c2, warm_s=w2,
        cluster_ticks_per_s=B * T * epochs / w2,
        compile_count=total_compile_count() - programs0,
        d2h_bytes_per_epoch=(d1 - d0) // epochs,
        state_bytes=pytree_nbytes(fixed.state),
        digests=[last1, fixed.last_digest], state=fixed.state,
        reports=[[r.goodput for r in m] for m in fixed.reports])
    return out


def print_fleet(label, res):
    for path in ("managed", "fixed"):
        r = res[path]
        print(f"{label}.{path}: B={r['B']} epochs={r['epochs']} "
              f"compile_s={r['compile_s']!r} warm_s={r['warm_s']!r} "
              f"cluster_ticks_per_s={r['cluster_ticks_per_s']!r} "
              f"compile_count={r['compile_count']} "
              f"d2h_bytes_per_epoch={r['d2h_bytes_per_epoch']} "
              f"state_bytes={r['state_bytes']}")


def compare_fleets(ref, got, label):
    for path in ("managed", "fixed"):
        a, b = ref[path], got[path]
        n_float = sum(compare_digests(x, y, f"{label}.{path}.digest[{e}]")
                      for e, (x, y) in enumerate(zip(a["digests"],
                                                     b["digests"])))
        bad = differing_int_leaves(a["state"], b["state"])
        require(not bad, f"{label}.{path}: state leaves differ: {bad}")
        require(a["reports"] == b["reports"], f"{label}.{path}: goodput")
        print(f"{label}.{path}: {len(a['digests'])} digests and "
              f"{len(a['state'])} state leaves match (integers exact, "
              f"{n_float} float digest leaves differ)")


# -------------------------------------------------------------- multiraft
def run_multiraft(cfg, *, shards, epochs, seed, backend):
    """Phase 4 body: one grouped Multi-Raft dispatch per run."""
    from repro.core.multiraft import MultiRaftSim
    mr = MultiRaftSim(cfg, shards=shards, seed=seed, backend=backend)
    reps = mr.run(epochs)
    f = mr.fleet
    return dict(digest=f.last_digest, group=f.last_group_digest,
                state=f.state,
                reports=[(r.writes_committed, r.reads_served,
                          r.two_pc_prepares) for r in reps])


def compare_multiraft(ref, got) -> int:
    """Pallas against XLA: member digests and integer state exactly, the
    grouped reduction's float sums to rtol 1e-4; returns how many float
    digest leaves differ."""
    n_float = compare_digests(ref["digest"], got["digest"], "multiraft") + \
        compare_digests(ref["group"], got["group"], "multiraft.group",
                        float_rtol=1e-4)
    bad = differing_int_leaves(ref["state"], got["state"])
    require(not bad, f"multiraft state leaves differ: {bad}")
    require(ref["reports"] == got["reports"], "multiraft reports")
    return n_float


# --------------------------------------------------------------------- kv
def run_kv(cfg, *, n_observers, n_keys, seed, backend):
    """Phase 5 body: puts, fenced and bounded-staleness reads, then the
    whole spot tier revoked (Property 3.4)."""
    import numpy as np
    from repro.core.runtime import BWRaftSim
    from repro.kvstore.service import BWKVService

    sim = BWRaftSim(cfg, write_rate=0.0, read_rate=8.0, seed=seed,
                    manage_resources=False, n_observers=n_observers,
                    staleness_bound=12, ae_interval=4, backend=backend)
    svc = BWKVService(sim)
    t0 = time.perf_counter()
    svc.put("kv/boot", 1)                         # waits for a leader
    sim.lease_fixed(3, 4)
    expect = {svc._key_id("kv/boot"): 1}
    keys = [f"kv/key{i:05d}" for i in range(n_keys)]
    for i, k in enumerate(keys):
        svc.put(k, 1000 + i)
        expect[svc._key_id(k)] = 1000 + i
    n_put = n_keys + 1
    fenced = sum(svc.get(k)[0] == expect[svc._key_id(k)] for k in keys)
    stale = sum(svc.get_stale(k)[0] == expect[svc._key_id(k)] for k in keys)
    require(fenced == n_keys, f"fenced get read back {fenced}/{n_keys}")
    require(stale == n_keys, f"get_stale read back {stale}/{n_keys}")

    spot = ~np.asarray(sim.static["is_voter"])
    spot_before = int(np.asarray(sim.state["alive"])[spot].sum())
    dobs_before = int(np.asarray(sim.state["dobs_alive"]).sum())
    sim.set_rates(phi=1.0)
    svc._step(1)
    sim.set_rates(phi=0.0)
    alive = np.asarray(sim.state["alive"])
    require(not alive[spot].any(), "a dense spot node survived phi=1")
    require(not np.asarray(sim.state["dobs_alive"]).any(),
            "a digest-tier slot survived phi=1")
    require(alive[~spot].all(), "phi=1 killed a voter")
    after = keys[:8]
    for i, k in enumerate(after):
        svc.put(k, 5000 + i)
        expect[svc._key_id(k)] = 5000 + i
    ok_after = sum(svc.get(k)[0] == expect[svc._key_id(k)] and
                   svc.get_stale(k)[0] == expect[svc._key_id(k)]
                   for k in after)
    require(ok_after == len(after),
            f"after revocation {ok_after}/{len(after)} read back")
    return dict(puts=n_put + len(after), gets=2 * (n_keys + len(after)),
                read_back=fenced + stale + 2 * ok_after,
                spot_killed=spot_before, dobs_killed=dobs_before,
                ticks=int(sim.state["tick"]),
                wall_s=time.perf_counter() - t0)


# ------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = check_device()

    import jax
    from repro import compile_cache
    from repro.configs.bwraft_kv import CONFIG
    from repro.core.state import pytree_nbytes
    from repro.kernels import resolve_backend
    from repro.kernels.raft_tick.ops import use_interpret

    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}")
    clock = compile_cache.clock()
    dev = jax.devices()[0]

    def peak():
        return dev.memory_stats()["peak_bytes_in_use"]

    cfg = dataclasses.replace(CONFIG, key_space=KEY_SPACE)
    print(f"config: {cfg.name} N={cfg.max_nodes} L={cfg.max_log} "
          f"K={cfg.key_space} T={cfg.period_ticks} "
          f"B={len(PHIS) * len(WRITE_RATES)}")

    def phase(name, fn):
        s0 = clock.snapshot()
        t0 = time.perf_counter()
        res = fn()
        s1 = clock.snapshot()
        print(f"phase {name}: wall_s={time.perf_counter() - t0!r} "
              f"compile_s={s1[0] - s0[0]!r} cache_hits={s1[1] - s0[1]} "
              f"cache_misses={s1[2] - s0[2]} peak_hbm_bytes={peak()}")
        return res

    for b in ("xla", "pallas"):
        print(f"backend {b!r} resolves to {resolve_backend(b)!r}")
    require(use_interpret() is False, "Pallas would run interpreted")
    print("pallas kernels: compiled (use_interpret() is False)")

    xla = phase("fleet_xla", lambda: run_fleet(
        cfg, PHIS, WRITE_RATES, backend="xla", epochs=EPOCHS,
        seed=args.seed, clock=clock))
    print_fleet("fleet_xla", xla)
    print(f"device state bytes (B=32 fleet): "
          f"{pytree_nbytes(xla['managed']['state'])}")

    pallas = phase("fleet_pallas", lambda: run_fleet(
        cfg, PHIS, WRITE_RATES, backend="pallas", epochs=EPOCHS,
        seed=args.seed, clock=clock))
    print_fleet("fleet_pallas", pallas)
    compare_fleets(xla, pallas, "fleet pallas vs xla")
    del xla, pallas

    def multiraft():
        ref = run_multiraft(cfg, shards=MR_SHARDS, epochs=MR_EPOCHS,
                            seed=args.seed, backend="xla")
        got = run_multiraft(cfg, shards=MR_SHARDS, epochs=MR_EPOCHS,
                            seed=args.seed, backend="pallas")
        return dict(got, n_float=compare_multiraft(ref, got))
    mr = phase("multiraft", multiraft)
    print(f"multiraft: shards={MR_SHARDS} epochs={MR_EPOCHS} pallas == xla "
          f"on integer leaves ({mr['n_float']} float digest leaves "
          f"differ); (writes_committed, reads_served, two_pc_prepares) "
          f"per epoch = {mr['reports']}")

    kv = phase("kv", lambda: run_kv(
        cfg, n_observers=KV_OBSERVERS, n_keys=KV_KEYS, seed=args.seed,
        backend="pallas"))
    print(f"kv: puts={kv['puts']} gets={kv['gets']} "
          f"read_back={kv['read_back']} spot_killed={kv['spot_killed']} "
          f"digest_slots_killed={kv['dobs_killed']} ticks={kv['ticks']} "
          f"wall_s={kv['wall_s']!r}")
    print(f"peak_hbm_bytes={peak()} compile_s_total={clock.seconds!r} "
          f"cache_hits={clock.hits} cache_misses={clock.misses}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
