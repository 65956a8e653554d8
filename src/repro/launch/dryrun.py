"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination
on 512 placeholder host devices, and extract the roofline raw terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Writes one JSON per combination into artifacts/dryrun/: cost_analysis FLOPs
and bytes (per-device: the compiled module is the SPMD per-device program),
memory_analysis, and the collective ops parsed from the partitioned HLO with
a per-op ICI byte estimate (ring cost model, group size from replica_groups).
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init. Do NOT set this in conftest/pyproject — only the dry-run
# needs 512 placeholder devices.
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.configs.shapes import (SHAPES, config_for_shape, input_specs,
                                  shape_supported)
from repro.launch import steps as steps_lib
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh, rules_for
from repro.models import model as model_lib

def _named(mesh, rules, axes_tree):
    def leaf(ax):
        return NamedSharding(mesh, rules.mesh_axes(ax))
    return jax.tree_util.tree_map(
        leaf, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x))


def run_one(arch: str, shape: str, mesh_kind: str, out_dir: str,
            verbose: bool = True, overrides: dict = None, tag: str = "") -> dict:
    cfg0 = get_config(arch)
    ok, why = shape_supported(cfg0, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind}
    if tag:
        rec["tag"] = tag
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _save(rec, out_dir)
        return rec
    cfg = config_for_shape(cfg0, shape)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    world = int(np.prod(mesh.devices.shape))
    gb = SHAPES[shape].global_batch
    rules = rules_for(cfg, mesh, gb)
    mode, specs, axes = input_specs(cfg0, shape)

    params_sds = jax.eval_shape(lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    p_axes = model_lib.param_axes(cfg, params_sds)
    params_sh = _named(mesh, rules, p_axes)
    in_sh = [_named(mesh, rules, axes[k]) for k in specs]
    arg_sds = [specs[k] for k in specs]

    step = steps_lib.make_step(mode, cfg, rules)
    t0 = time.time()
    total, active = model_lib.count_params(cfg)
    rec.update({
        "mode": mode, "world": world,
        "params_total": total, "params_active": active,
        "seq_len": SHAPES[shape].seq_len, "global_batch": gb,
        "rules": {k: (list(v) if isinstance(v, (list, tuple)) else v)
                   for k, v in rules.rules.items()},
    })
    try:
        if mode == "train":
            lr_sds = jax.ShapeDtypeStruct((), np.float32)
            jitted = jax.jit(step, in_shardings=(params_sh, in_sh[0], None))
            with mesh:
                lowered = jitted.lower(params_sds, arg_sds[0], lr_sds)
        elif mode in ("prefill", "encode"):
            jitted = jax.jit(step, in_shardings=(params_sh, in_sh[0]))
            with mesh:
                lowered = jitted.lower(params_sds, arg_sds[0])
        else:  # decode: (params, cache, tokens, pos)
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, in_sh[0], in_sh[1], None))
            with mesh:
                lowered = jitted.lower(params_sds, arg_sds[0], arg_sds[1],
                                       jax.ShapeDtypeStruct((), np.int32))
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {a: int(getattr(mem, a)) for a in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes") if hasattr(mem, a)}
        except Exception as e:  # CPU backend may not implement it
            mem_rec = {"error": str(e)}
        text = compiled.as_text()
        t0 = time.time()
        hc = hlo_cost.analyze(text, world)  # trip-count-aware (see hlo_cost.py)
        t_analyze = time.time() - t0
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "analyze_s": round(t_analyze, 2),
            "flops_per_device": hc["flops_per_device"],
            "bytes_per_device": hc["bytes_per_device"],
            "collective_ici_bytes": hc["ici_bytes_per_device"],
            "transcendentals_per_device": hc["transcendentals"],
            "collectives": hc["collectives"],
            "unparsed_loops": hc["unparsed_loops"],
            # XLA's own (loop-body-once) numbers, for reference
            "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                                  if isinstance(v, (int, float)) and not k.startswith("utilization")},
            "memory_analysis": mem_rec,
            "n_collectives": int(sum(s["count"] for s in hc["collectives"].values())),
            "hlo_lines": text.count("\n"),
        })
        if verbose:
            print(f"[dryrun] {arch} x {shape} x {mesh_kind}: OK "
                  f"flops/dev={rec['flops_per_device']:.3e} "
                  f"ici={rec['collective_ici_bytes']:.3e}B "
                  f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape} x {mesh_kind}: FAIL {rec['error']}")
    _save(rec, out_dir)
    return rec


def _save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="", help="suffix for artifact filenames")
    ap.add_argument("--scan-groups", type=int, default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--remat", default=None, choices=["none", "full", "dots"])
    ap.add_argument("--dispatch-groups", type=int, default=None)
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=None)
    args = ap.parse_args()

    overrides = {}
    if args.scan_groups is not None:
        overrides["scan_groups"] = args.scan_groups
    if args.seq_shard:
        overrides["seq_shard"] = True
    if args.remat is not None:
        overrides["remat"] = args.remat
    if args.dispatch_groups is not None:
        overrides["dispatch_groups"] = args.dispatch_groups
    if args.pure_dp:
        overrides["pure_data_parallel"] = True
    if args.grad_accum is not None:
        overrides["grad_accum"] = args.grad_accum

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]

    results = []
    for m in meshes:
        for a in archs:
            for s in shapes:
                results.append(run_one(a, s, m, args.out,
                                       overrides=overrides or None,
                                       tag=args.tag))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
