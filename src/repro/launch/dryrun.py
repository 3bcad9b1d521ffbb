import os
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=512"]).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  For each cell this proves the distribution config is
coherent on the production mesh — sharding mismatches, compile-time OOM or
unsupported collectives fail here — and records memory_analysis(),
cost_analysis() and the collective-op inventory for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES_BY_NAME, shape_applicable
from repro.launch import steps as S
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh, HW
from repro.models.common import abstract_tree, param_count
from repro.sharding import axes as axes_mod


def input_specs(arch: str, shape_name: str, *, mesh=None, runcfg=None):
    """ShapeDtypeStruct stand-ins (+ NamedShardings) for every model input
    of the given cell: (step_kind, args, in_shardings, donate)."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    runcfg = runcfg or S.default_runcfg(cfg, shape)
    mesh = mesh if mesh is not None else make_production_mesh()
    rules = S.resolve_rules(cfg, runcfg.sharding_profile)
    log = axes_mod.PruneLog()

    def shardings(spec_tree):
        return axes_mod.tree_shardings(spec_tree, rules, mesh, prune_log=log)

    bspecs = S.batch_specs(cfg, shape)
    if shape.kind != "train":
        bspecs.pop("labels", None)
    batch = abstract_tree(bspecs)
    batch_sh = shardings(bspecs)

    if shape.kind == "train":
        st_specs = S.train_state_specs(cfg, runcfg)
        args = (abstract_tree(st_specs), batch)
        shs = (shardings(st_specs), batch_sh)
        donate = (0,)
    elif shape.kind == "prefill":
        p_specs = S.param_specs(cfg, runcfg)
        args = (abstract_tree(p_specs), batch)
        shs = (shardings(p_specs), batch_sh)
        donate = ()
    else:  # decode
        p_specs = S.param_specs(cfg, runcfg)
        d_specs = S.decode_state_specs(cfg, shape, runcfg)
        tok_spec = {"tokens": S.batch_specs(cfg, shape)["tokens"]}
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), np.int32)
        args = (abstract_tree(p_specs), abstract_tree(d_specs), tok)
        tok_sh = axes_mod.tree_shardings(
            {"t": S.batch_specs(cfg, shape)["tokens"]._replace(
                shape=(shape.global_batch, 1))}, rules, mesh,
            prune_log=log)["t"]
        shs = (shardings(p_specs), shardings(d_specs), tok_sh)
        donate = (1,)
    return shape.kind, args, shs, donate, runcfg, rules, log


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             runcfg_overrides=None, verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    runcfg = S.default_runcfg(cfg, shape, **(runcfg_overrides or {}))
    kind, args, shs, donate, runcfg, rules, log = input_specs(
        arch, shape_name, mesh=mesh, runcfg=runcfg)
    step, _ = S.make_step(cfg, runcfg, mesh, kind)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(step, in_shardings=shs, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    txt = compiled.as_text()
    colls = hlo_stats.collective_stats(txt)
    n_chips = int(np.prod(list(mesh.shape.values())))

    rec = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": dict(mesh.shape), "status": "OK",
        "params": param_count(S.param_specs(cfg, runcfg)),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_dev": ca.get("flops", 0.0),
        "bytes_per_dev": ca.get("bytes accessed", 0.0),
        "collective_bytes_per_dev": int(
            sum(v["wire_bytes"] for v in colls.values())),
        "collectives": {k: {"count": int(v["count"]),
                            "result_mb": round(v["result_bytes"] / 1e6, 2),
                            "wire_mb": round(v["wire_bytes"] / 1e6, 2)}
                        for k, v in colls.items()},
        "memory": {
            "argument_mb": round(ma.argument_size_in_bytes / 2**20, 1),
            "output_mb": round(ma.output_size_in_bytes / 2**20, 1),
            "temp_mb": round(ma.temp_size_in_bytes / 2**20, 1),
            "alias_mb": round(ma.alias_size_in_bytes / 2**20, 1),
        },
        "hbm_total_mb": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**20, 1),
        "sharding_fallbacks": log.entries,
    }
    if verbose:
        fits = rec["hbm_total_mb"] * 2**20 <= HW["hbm_bytes"]
        print(f"[{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}]"
              f" OK compile={t_compile:.1f}s")
        print(f"  memory_analysis: args={rec['memory']['argument_mb']}MB "
              f"out={rec['memory']['output_mb']}MB "
              f"temp={rec['memory']['temp_mb']}MB "
              f"alias={rec['memory']['alias_mb']}MB "
              f"-> {rec['hbm_total_mb']}MB/dev "
              f"({'fits' if fits else 'OVER'} {HW['hbm_bytes']/2**30:.0f}GB)")
        print(f"  cost_analysis: flops/dev={rec['flops_per_dev']:.3e} "
              f"bytes/dev={rec['bytes_per_dev']:.3e}")
        print(hlo_stats.render_stats(colls))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    shapes = sorted(SHAPES_BY_NAME) if (args.all or not args.shape) \
        else (args.shape,)
    meshes = (False, True) if (args.both_meshes or args.all) \
        else (args.multi_pod,)
    records = []
    failed = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, multi_pod=mp)
                except Exception as e:  # a failure here is a sharding bug
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
                    failed += 1
                records.append(rec)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, default=str)
    print(f"\n{sum(r['status'] == 'OK' for r in records)} OK, "
          f"{sum(r['status'] == 'SKIP' for r in records)} SKIP, "
          f"{failed} FAIL / {len(records)} cells")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
