#!/usr/bin/env python
"""Consensus-tick kernel benchmark: pallas vs xla vs reference.

Times the FOUR Pallas kernel families (DESIGN.md §8) and the
end-to-end protocol tick on every formulation the repo carries:

  per kernel    each Pallas op against its frozen `ref.py` twin, at
                the paper cluster's shapes:
                  raft_tick       log_match_append / commit_majority /
                                  apply_last_wins
                  leader_fanout   fused budgeted AppendEntries fan-out
                  group_digest    blockwise masked group reduction
                  ae_sync         fused anti-entropy round
  end to end    a jitted T-tick scan of `step.tick` on
                backend="pallas", backend="xla" (the PR-2 fast path),
                and reference=True (the PR-1 baseline).

Before timing, every kernel family is checked **bit-identical**
against its ref twin on random operands, and the three end-to-end
trajectories are checked bit-identical from the same seed — the run
FAILS (exit 1) if any output or state leaf diverges, so CI catches
kernel-contract regressions even on machines where the timings
themselves are noise.

Emits ``BENCH_tick.json``.  Interpret-mode caveat: on CPU the pallas
numbers measure the Pallas *interpreter* traced into XLA, not kernel
speed (DESIGN.md §8).  Every timing block therefore carries an
explicit ``"interpreted": true/false`` field — when it is true the
pallas ratios are NOT kernel speedups and no perf ceiling is enforced.

  PYTHONPATH=src python benchmarks/perf_tick.py [--smoke] [--out PATH]

``--smoke`` shrinks the cluster and iteration counts for CI.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.bwraft_kv import CONFIG
from repro.core import state as state_mod
from repro.core import step as step_mod
from repro.core.cluster_config import ClusterConfig, SiteConfig
from repro.core.runtime import make_cfg_arrays
from repro.kernels.ae_sync import ops as ae_ops
from repro.kernels.ae_sync import ref as ae_ref
from repro.kernels.group_digest import ops as gd_ops
from repro.kernels.group_digest import ref as gd_ref
from repro.kernels.leader_fanout import ops as lf_ops
from repro.kernels.leader_fanout import ref as lf_ref
from repro.kernels.raft_tick import ops as rt_ops
from repro.kernels.raft_tick import ref as rt_ref
from repro import compile_cache

SMOKE_CONFIG = ClusterConfig(
    name="bwraft-kv-smoke",
    sites=(SiteConfig("s0", followers=2, rtt_intra=1, rtt_inter=6,
                      on_demand_price=0.0416, spot_price_mean=0.0125),
           SiteConfig("s1", followers=1, rtt_intra=1, rtt_inter=8,
                      on_demand_price=0.0416, spot_price_mean=0.0125)),
    period_ticks=40, max_log=256, key_space=128,
    max_secretaries=2, max_observers=4)


def _timeit(fn, *args, iters: int, warmup: int = 1) -> float:
    """Median wall seconds per call of a jitted fn (post-compile)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def _kernel_inputs(cfg: ClusterConfig, static, seed: int = 0):
    """Plausible operands at the cluster's real shapes (the equivalence
    itself is enforced on full trajectories below and in tests)."""
    rng = np.random.default_rng(seed)
    N, L, K = static["N"], cfg.max_log, cfg.key_space
    A, W = static["max_apply"], static["max_ship"]
    mk = lambda hi, sh: jnp.asarray(rng.integers(0, hi, sh), jnp.int32)
    return {
        "log_match": dict(
            log_term=mk(3, (N, L)), log_key=mk(K, (N, L)),
            log_val=mk(2**20, (N, L)), ldr_term=mk(3, (L,)),
            ldr_key=mk(K, (L,)), ldr_val=mk(2**20, (L,)),
            log_len=mk(L + 1, (N,)), app_from_len=mk(L + 1, (N,)),
            app_upto=mk(L + 1, (N,)),
            due=jnp.asarray(rng.random(N) < 0.5)),
        "commit": dict(
            match_len=mk(L + 1, (N,)),
            voter_alive=jnp.asarray(static["is_voter"]),
            ldr_term=mk(3, (L,)), ldr_cur_term=jnp.int32(1),
            majority=jnp.int32(static["majority"])),
        "apply": dict(
            kv=mk(2**20, (N, K)), keys=mk(K, (N, A)),
            vals=mk(2**20, (N, A)),
            valid=jnp.asarray(rng.random((N, A)) < 0.7)),
        "W": W,
    }


def _wide_inputs(cfg: ClusterConfig, static, seed: int = 1):
    """Random operands for the PR-9 families, at the cluster's real
    shapes (property sweeps live in tests/test_wide_kernels.py)."""
    rng = np.random.default_rng(seed)
    N, L = static["N"], cfg.max_log
    # the tick static carries no digest-tier slots; provision some so
    # the ae_sync family benches at a real observer width
    static_o = state_mod.build_static(
        cfg, n_obs_digest=max(cfg.max_observers, 2))
    O = len(static_o["dobs_site"])
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    mk = lambda lo, hi, sh: i32(rng.integers(lo, hi, sh))
    fanout = dict(
        role=mk(0, 6, (N,)), alive=jnp.asarray(rng.random(N) < 0.8),
        warn_timer=mk(-1, 5, (N,)), sec_of=mk(-1, N, (N,)),
        match_len=mk(0, L + 1, (N,)), app_arrive_t=mk(-1, 40, (N,)),
        app_from_len=mk(0, L + 1, (N,)), app_upto=mk(0, L + 1, (N,)),
        app_term=mk(0, 4, (N,)), app_commit=mk(0, L + 1, (N,)),
        rtt=jnp.asarray(static["rtt"], jnp.int32),
        lid_c=jnp.int32(0), has_leader=jnp.asarray(True),
        tick=jnp.int32(7), ldr_len=jnp.int32(L), ldr_term=jnp.int32(2),
        ldr_commit=jnp.int32(L // 2))
    B, G, H = 32, 5, 64
    group = dict(
        gids=mk(0, G + 1, (B,)),            # == G rows drop (ragged)
        int_mat=mk(0, 2**20, (B, 2 * H + 9)),
        flt_mat=jnp.asarray(
            rng.standard_normal((B, 3)) * 100.0, jnp.float32))
    ae = dict(
        dobs_alive=mk(0, 2, (O,)), dobs_fol=mk(-1, N, (O,)),
        dobs_applied=mk(0, L, (O,)), dobs_term=mk(0, 4, (O,)),
        dobs_digest=jnp.asarray(
            rng.integers(0, 2**32, O, dtype=np.uint32)),
        dobs_synced_t=mk(-1, 40, (O,)), ae_phase=mk(0, 4, (O,)),
        dobs_site=i32(static_o["dobs_site"]),
        alive=jnp.asarray(rng.random(N) < 0.8),
        is_voter=jnp.asarray(static["is_voter"]),
        applied_len=mk(0, L + 1, (N,)), term=mk(0, 4, (N,)),
        applied_digest=jnp.asarray(
            rng.integers(0, 2**32, N, dtype=np.uint32)),
        site=i32(static["site"]),
        site_rtt=jnp.asarray(static_o["site_rtt"], jnp.int32),
        tick=jnp.int32(12), ae_interval=jnp.int32(4))
    return {"leader_fanout": fanout, "group_digest": group,
            "ae_sync": ae}


def bench_kernels(cfg: ClusterConfig, static, iters: int):
    """raft_tick ops vs ref twins; returns timing blocks (the raft_tick
    family's bit-identity gate is the trajectory check in bench_tick)."""
    inp = _kernel_inputs(cfg, static)
    W = inp["W"]
    interpret = rt_ops.use_interpret()
    # positional arg tuples (dict pytrees re-order under jit)
    pairs = {
        "log_match_append": (
            jax.jit(lambda *a: rt_ops.log_match_append(*a, w=W)),
            jax.jit(lambda *a: rt_ref.log_match_append_ref(*a, w=W)),
            tuple(inp["log_match"].values())),
        "commit_majority": (
            jax.jit(rt_ops.commit_majority),
            jax.jit(rt_ref.commit_majority_ref),
            tuple(inp["commit"].values())),
        "apply_last_wins": (
            jax.jit(rt_ops.apply_last_wins),
            jax.jit(rt_ref.apply_last_wins_ref),
            tuple(inp["apply"].values())),
    }
    out = {}
    for name, (pallas_fn, ref_fn, args_t) in pairs.items():
        p_ms = _timeit(pallas_fn, *args_t, iters=iters) * 1e3
        r_ms = _timeit(ref_fn, *args_t, iters=iters) * 1e3
        out[name] = {"pallas_ms": p_ms, "ref_ms": r_ms,
                     "pallas_vs_ref": r_ms / max(p_ms, 1e-12),
                     "interpreted": interpret}
    return out


def bench_wide_kernels(cfg: ClusterConfig, static, iters: int):
    """PR-9 families (fan-out / digest reduction / anti-entropy) vs ref
    twins; returns (timing blocks, equal: bool) — the bit-identity gate
    compares every output array exactly."""
    inp = _wide_inputs(cfg, static)
    interpret = rt_ops.use_interpret()
    knobs = dict(msg_budget=static["msg_budget"],
                 max_ship=static["max_ship"],
                 entries_per_msg=static["entries_per_msg"])
    G = 5
    u2i = lambda v: jax.lax.bitcast_convert_type(v, jnp.int32)

    def ae_ref_fn(*a):
        # ref twin works on int32 digest views (ops.py owns the bitcast)
        (da, df, dap, dt, dg, ds, ph, dsi, al, iv, apl, tm, adg, st,
         srtt, tick, itv) = a
        out = ae_ref.ae_sync_ref(da, df, dap, dt, u2i(dg), ds, ph, dsi,
                                 al, iv, apl, tm, u2i(adg), st, srtt,
                                 tick, itv)
        return (out[0], out[1],
                jax.lax.bitcast_convert_type(out[2], jnp.uint32), out[3])

    pairs = {
        "leader_fanout": (
            lambda *a: lf_ops.leader_fanout(*a, **knobs),
            jax.jit(lambda *a: lf_ref.leader_fanout_ref(*a, **knobs)),
            tuple(inp["leader_fanout"].values())),
        "group_digest": (
            lambda *a: gd_ops.group_reduce(*a, n_groups=G),
            jax.jit(lambda *a: gd_ref.group_reduce_ref(*a, n_groups=G)),
            tuple(inp["group_digest"].values())),
        "ae_sync": (
            ae_ops.ae_sync,
            jax.jit(ae_ref_fn),
            tuple(inp["ae_sync"].values())),
    }
    out, equal = {}, True
    for name, (pallas_fn, ref_fn, args_t) in pairs.items():
        got = jax.tree.map(np.asarray, pallas_fn(*args_t))
        want = jax.tree.map(np.asarray, ref_fn(*args_t))
        fam_eq = all(np.array_equal(g, w) for g, w in zip(got, want))
        equal &= fam_eq
        p_ms = _timeit(pallas_fn, *args_t, iters=iters) * 1e3
        r_ms = _timeit(ref_fn, *args_t, iters=iters) * 1e3
        out[name] = {"pallas_ms": p_ms, "ref_ms": r_ms,
                     "pallas_vs_ref": r_ms / max(p_ms, 1e-12),
                     "bit_identical": fam_eq, "interpreted": interpret}
    return out, equal


def bench_tick(cfg: ClusterConfig, static, T: int, iters: int):
    """End-to-end T-tick scans; returns (timing blocks, equal: bool)."""
    cfg_c = make_cfg_arrays(cfg, write_rate=8.0, read_rate=16.0, phi=0.02)
    state0 = state_mod.init_state(cfg, static)
    rngs = jax.random.split(jax.random.PRNGKey(0), T)
    interpret = rt_ops.use_interpret()

    def scan_fn(reference, backend):
        def body(c, r):
            s, _ = step_mod.tick(c, static, cfg_c, r, reference=reference,
                                 backend=backend)
            return s, None
        return jax.jit(lambda s: jax.lax.scan(body, s, rngs)[0])

    variants = {"xla": (scan_fn(False, "xla"), False),
                "pallas": (scan_fn(False, "pallas"), interpret),
                "reference": (scan_fn(True, "xla"), False)}
    finals, timings = {}, {}
    for name, (fn, interp) in variants.items():
        finals[name] = jax.tree.map(np.asarray, fn(state0))
        timings[name] = {
            "ms_per_tick": _timeit(fn, state0, iters=iters) * 1e3 / T,
            "interpreted": interp}
    equal = all(
        np.array_equal(finals["xla"][k], finals[v][k])
        for v in ("pallas", "reference") for k in finals["xla"])
    timings["speedup_xla_vs_reference"] = \
        timings["reference"]["ms_per_tick"] / timings["xla"]["ms_per_tick"]
    timings["pallas_vs_xla"] = \
        timings["xla"]["ms_per_tick"] / timings["pallas"]["ms_per_tick"]
    return timings, equal


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small cluster + few iters for CI (equivalence "
                         "gates only, timings informational)")
    ap.add_argument("--out", default="BENCH_tick.json")
    args = ap.parse_args(argv)

    cfg = SMOKE_CONFIG if args.smoke else CONFIG
    static = state_mod.build_static(cfg)
    T = cfg.period_ticks
    k_iters, t_iters = (3, 2) if args.smoke else (10, 3)
    interpret = rt_ops.use_interpret()
    print(f"=== pallas kernel layer: {cfg.name} N={static['N']} "
          f"L={cfg.max_log} K={cfg.key_space} T={T} "
          f"(pallas {'interpret' if interpret else 'compiled'}) ===")

    kernels = bench_kernels(cfg, static, k_iters)
    wide, wide_equal = bench_wide_kernels(cfg, static, k_iters)
    kernels.update(wide)
    for name, r in kernels.items():
        gate = "" if r.get("bit_identical", True) else "  DIVERGED"
        print(f"{name:>18}: pallas {r['pallas_ms']:8.2f} ms   "
              f"ref {r['ref_ms']:8.2f} ms{gate}")

    tick, equal = bench_tick(cfg, static, T, t_iters)
    print(f"{'tick (end-to-end)':>18}: "
          f"xla {tick['xla']['ms_per_tick']:.3f} ms/tick   "
          f"pallas {tick['pallas']['ms_per_tick']:.3f}   "
          f"reference {tick['reference']['ms_per_tick']:.3f}")
    print(f"trajectories bit-identical: {equal}   "
          f"wide kernels bit-identical: {wide_equal}")

    result = {
        "config": {"cluster": cfg.name, "N": int(static["N"]),
                   "L": cfg.max_log, "K": cfg.key_space,
                   "W": int(static["max_ship"]),
                   "A": int(static["max_apply"]), "T": T,
                   "smoke": args.smoke,
                   "jax_backend": jax.default_backend(),
                   "interpret": interpret},
        "kernels": kernels,
        "tick": tick,
        "equivalence": {
            "pallas_equals_xla_equals_reference": equal,
            "wide_kernels_equal_ref": wide_equal},
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"-> {args.out}")

    if not equal or not wide_equal:
        print("FAIL: a kernel formulation diverged from its twin",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
