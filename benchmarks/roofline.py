import os
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=512"]).strip()

"""Roofline analysis (assignment §g): derive the three roofline terms per
(arch x shape x mesh) from compiled artifacts.

Accounting (DESIGN.md §5): XLA's HLO cost analysis counts scan bodies
once, so this harness lowers *unrolled* programs at depth L0 = one
repeating period and L1 = two periods, and extrapolates
    cost(L) = c(L0) + (L - L0)/P * (c(L1) - c(L0)).
Training costs are measured per microbatch (grad+opt with the microbatch
slice) plus a separate optimizer-only program so the grad-accumulation
step total is  mb * c_micro - (mb-1) * c_opt  (exact).  Collective wire
bytes come from the unrolled HLO text (launch/hlo_stats.py).

Terms (per device, seconds):
    compute    = HLO_flops / 197e12        (TPU v5e bf16 peak)
    memory     = HLO_bytes / 819e9         (HBM bandwidth)
    collective = wire_bytes / 50e9         (per-link ICI)
MODEL_FLOPS = 6·N·D (train) / 2·N·D (prefill) / 2·N_active·B (decode).

``--raft`` instead rooflines the consensus hot paths of the widened
Pallas kernel layer (DESIGN.md §8): the leader fan-out and the grouped
digest reduction, lowered from their XLA formulations at the paper
cluster / fleet shapes — bytes, FLOPs, arithmetic intensity, and where
each lands against the TPU v5e ridge point.

Usage: python -m benchmarks.roofline [--arch A --shape S] [--all]
       [--json out.json] [--profile train_sp] [--microbatches N]
       [--raft] ...
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import numpy as np
import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES_BY_NAME, shape_applicable
from repro.launch import hlo_stats
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh, HW
from repro.models.common import abstract_tree, param_count
from repro.optim import adamw
from repro.sharding import axes as axes_mod

CHIPS = 256


def model_flops(cfg, shape) -> float:
    """Assignment formula: 6ND dense / 6·N_active·D MoE (per step, global)."""
    runcfg = S.default_runcfg(cfg, shape)
    n_total = param_count(S.param_specs(cfg, runcfg))
    n_active = n_total
    if cfg.moe_num_experts:
        from repro.models.moe import padded_experts
        E = padded_experts(cfg.moe_num_experts)
        per_expert = 3 * cfg.d_model * cfg.moe_d_ff
        n_moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
        routed = E * per_expert * n_moe_layers
        used = cfg.moe_top_k * per_expert * n_moe_layers
        n_active = n_total - routed + used
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n_active * d
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 token


def _lower_cost(step, args, shs, donate, mesh):
    with mesh:
        compiled = jax.jit(step, in_shardings=shs,
                           donate_argnums=donate).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    txt = compiled.as_text()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "wire": float(hlo_stats.total_collective_bytes(txt)),
        "colls": hlo_stats.collective_stats(txt),
    }


def _opt_cost(cfg, runcfg, mesh, rules):
    """Optimizer-only program (adamw update with zero grads)."""
    ps = S.param_specs(cfg, runcfg)
    opt = adamw.abstract_opt_state(ps, S.DTYPES[runcfg.opt_state_dtype])
    log = axes_mod.PruneLog()
    sh = (axes_mod.tree_shardings(ps, rules, mesh, prune_log=log),
          axes_mod.tree_shardings(ps, rules, mesh),
          axes_mod.tree_shardings(opt, rules, mesh))

    def opt_step(params, grads, opt_state):
        return adamw.adamw_update(params, grads, opt_state,
                                  lr=1e-3, grad_clip=1.0)

    args = (abstract_tree(ps), abstract_tree(ps), abstract_tree(opt))
    return _lower_cost(opt_step, args, sh, (0, 2), mesh)


def analyse_cell(arch: str, shape_name: str, *, runcfg_overrides=None,
                 verbose=True):
    cfg_full = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg_full, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "reason": why}
    mesh = make_production_mesh()
    overrides = dict(runcfg_overrides or {})
    mb = overrides.pop("num_microbatches", None)
    runcfg = S.default_runcfg(cfg_full, shape, scan_layers=False,
                              unroll_attn=True, num_microbatches=1,
                              **overrides)
    if mb is None:
        mb = S.default_runcfg(cfg_full, shape).num_microbatches \
            if shape.kind == "train" else 1
    rules = S.resolve_rules(cfg_full, runcfg.sharding_profile)

    P = cfg_full.layer_period
    L0, L1 = P, 2 * P
    t0 = time.time()
    costs = []
    for L in (L0, L1):
        cfg = cfg_full.with_layers(L)
        if shape.kind == "train":
            # per-microbatch slice
            micro = dataclasses.replace(shape,
                                        global_batch=shape.global_batch // mb)
            from repro.launch.dryrun import input_specs
            kind, args, shs, donate, rc, _, _ = input_specs(
                arch, shape_name, mesh=mesh, runcfg=runcfg)
            # rebuild with reduced depth + microbatch slice
            c = _cell_cost(cfg, micro, runcfg, mesh)
        else:
            c = _cell_cost(cfg, shape, runcfg, mesh)
        costs.append(c)
    c0, c1 = costs
    L_full = cfg_full.num_layers
    scale = (L_full - L0) / (L1 - L0)

    def extrap(key):
        return c0[key] + scale * (c1[key] - c0[key])

    flops = extrap("flops")
    nbytes = extrap("bytes")
    wire = extrap("wire")
    if shape.kind == "train" and mb > 1:
        co = _opt_cost(cfg_full, runcfg, mesh, rules)
        flops = mb * flops - (mb - 1) * co["flops"]
        nbytes = mb * nbytes - (mb - 1) * co["bytes"]
        wire = mb * wire - (mb - 1) * co["wire"]

    compute_t = flops / HW["peak_flops_bf16"]
    memory_t = nbytes / HW["hbm_gbps"]
    coll_t = wire / HW["ici_link_gbps"]
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg_full, shape) / CHIPS
    rec = {
        "arch": arch, "shape": shape_name, "status": "OK",
        "mesh": "16x16", "profile": runcfg.sharding_profile,
        "microbatches": mb,
        "flops_per_dev": flops, "bytes_per_dev": nbytes,
        "collective_bytes_per_dev": wire,
        "compute_s": compute_t, "memory_s": memory_t,
        "collective_s": coll_t,
        "bottleneck": bottleneck,
        "model_flops_per_dev": mf,
        "useful_flops_frac": mf / max(flops, 1e-9),
        "roofline_fraction": compute_t / max(max(terms.values()), 1e-12),
        "analyse_s": round(time.time() - t0, 1),
    }
    if verbose:
        print(f"[{arch} x {shape_name}] compute={compute_t*1e3:8.2f}ms "
              f"memory={memory_t*1e3:8.2f}ms coll={coll_t*1e3:8.2f}ms "
              f"-> {bottleneck}-bound  useful={rec['useful_flops_frac']:.2f} "
              f"roofline_frac={rec['roofline_fraction']:.2f}")
    return rec


def _cell_cost(cfg, shape, runcfg, mesh):
    """Lower one program for a (possibly depth-reduced) cfg and shape."""
    from repro.launch.dryrun import input_specs as _  # noqa — shared logic
    rules = S.resolve_rules(cfg, runcfg.sharding_profile)
    log = axes_mod.PruneLog()

    def shardings(t):
        return axes_mod.tree_shardings(t, rules, mesh, prune_log=log)

    bspecs = S.batch_specs(cfg, shape)
    if shape.kind != "train":
        bspecs.pop("labels", None)
    batch = abstract_tree(bspecs)
    batch_sh = shardings(bspecs)
    if shape.kind == "train":
        st = S.train_state_specs(cfg, runcfg)
        step, _r = S.make_train_step(cfg, runcfg, mesh)
        return _lower_cost(step, (abstract_tree(st), batch),
                           (shardings(st), batch_sh), (0,), mesh)
    if shape.kind == "prefill":
        ps = S.param_specs(cfg, runcfg)
        step, _r = S.make_prefill_step(cfg, runcfg, mesh)
        return _lower_cost(step, (abstract_tree(ps), batch),
                           (shardings(ps), batch_sh), (), mesh)
    ps = S.param_specs(cfg, runcfg)
    ds = S.decode_state_specs(cfg, shape, runcfg)
    step, _r = S.make_decode_step(cfg, runcfg, mesh)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), np.int32)
    tok_sh = axes_mod.tree_shardings(
        {"t": S.batch_specs(cfg, shape)["tokens"]._replace(
            shape=(shape.global_batch, 1))}, rules, mesh)["t"]
    return _lower_cost(step, (abstract_tree(ps), abstract_tree(ds), tok),
                       (shardings(ps), shardings(ds), tok_sh), (1,), mesh)


def _raft_cost(fn, *args):
    """flops / bytes-accessed for one jitted consensus op."""
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def analyse_raft_kernels(verbose=True):
    """Roofline terms for the §8 fan-out and digest-reduction paths.

    Lowers the XLA formulations (the kernels' bit-identical twins, so
    the operand traffic is the same) at the paper cluster's node count
    and the B=32 fleet's digest shapes, and reports bytes, FLOPs,
    arithmetic intensity, and the v5e ridge-point verdict — integer
    select/reduce work this sparse is memory-bound, which is the
    argument for fusing it (one pass, no gather/scatter HLO)."""
    import jax.numpy as jnp
    from repro.configs.bwraft_kv import CONFIG as RAFT_CONFIG
    from repro.core import state as raft_state
    from repro.kernels.group_digest import ref as gd_ref
    from repro.kernels.leader_fanout import ref as lf_ref

    rng = np.random.default_rng(0)
    static = raft_state.build_static(RAFT_CONFIG)
    N, L = static["N"], RAFT_CONFIG.max_log
    mk = lambda lo, hi, sh: jnp.asarray(rng.integers(lo, hi, sh),
                                        jnp.int32)
    fan_args = (mk(0, 6, (N,)), jnp.asarray(rng.random(N) < 0.9),
                mk(-1, 5, (N,)), mk(-1, N, (N,)), mk(0, L + 1, (N,)),
                mk(-1, 40, (N,)), mk(0, L + 1, (N,)), mk(0, L + 1, (N,)),
                mk(0, 4, (N,)), mk(0, L + 1, (N,)),
                jnp.asarray(static["rtt"], jnp.int32), jnp.int32(0),
                jnp.asarray(True), jnp.int32(7), jnp.int32(L),
                jnp.int32(2), jnp.int32(L // 2))
    knobs = dict(msg_budget=static["msg_budget"],
                 max_ship=static["max_ship"],
                 entries_per_msg=static["entries_per_msg"])
    B, G, H = 32, 8, 64
    grp_args = (mk(0, G + 1, (B,)), mk(0, 2**20, (B, 2 * H + 9)),
                jnp.asarray(rng.standard_normal((B, 3)), jnp.float32))

    ridge = HW["peak_flops_bf16"] / HW["hbm_gbps"]   # FLOPs per byte
    records = []
    for name, cost, shape in (
            ("leader_fanout",
             _raft_cost(lambda *a: lf_ref.leader_fanout_ref(*a, **knobs),
                        *fan_args),
             f"N={N} rtt={N}x{N}"),
            ("group_digest",
             _raft_cost(lambda *a: gd_ref.group_reduce_ref(*a, n_groups=G),
                        *grp_args),
             f"B={B} G={G} F={2 * H + 9}+3")):
        ai = cost["flops"] / max(cost["bytes"], 1e-9)
        rec = {"kernel": name, "status": "OK", "shape": shape,
               "flops": cost["flops"], "bytes": cost["bytes"],
               "arith_intensity": ai, "ridge_flops_per_byte": ridge,
               "bound": "memory" if ai < ridge else "compute",
               "memory_s": cost["bytes"] / HW["hbm_gbps"],
               "compute_s": cost["flops"] / HW["peak_flops_bf16"]}
        records.append(rec)
        if verbose:
            print(f"[raft {name:>14}] {shape:<22} "
                  f"flops={cost['flops']:12.0f} bytes={cost['bytes']:10.0f} "
                  f"AI={ai:7.3f} ridge={ridge:.0f} -> {rec['bound']}-bound")
    return records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--profile", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--raft", action="store_true",
                    help="roofline the consensus fan-out and digest-"
                         "reduction paths instead of the model cells")
    args = ap.parse_args(argv)

    if args.raft:
        records = analyse_raft_kernels()
        if args.json:
            with open(args.json, "w") as f:
                json.dump(records, f, indent=1, default=str)
        print(f"{len(records)} raft kernels analysed")
        return 0

    overrides = {}
    if args.profile:
        overrides["sharding_profile"] = args.profile
    if args.microbatches:
        overrides["num_microbatches"] = args.microbatches
    if args.remat_policy:
        overrides["remat_policy"] = args.remat_policy
    if args.attn_chunk:
        overrides["attn_chunk_q"] = args.attn_chunk
        overrides["attn_chunk_k"] = args.attn_chunk

    archs = ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    shapes = sorted(SHAPES_BY_NAME) if (args.all or not args.shape) \
        else (args.shape,)
    records = []
    for a in archs:
        for s in shapes:
            try:
                records.append(analyse_cell(a, s,
                                            runcfg_overrides=overrides))
            except Exception as e:
                traceback.print_exc()
                records.append({"arch": a, "shape": s, "status": "FAIL",
                                "error": f"{type(e).__name__}: {e}"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, default=str)
    n_fail = sum(r["status"] == "FAIL" for r in records)
    print(f"{len(records)} cells, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
