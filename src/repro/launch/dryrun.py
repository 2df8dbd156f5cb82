import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with abstract inputs (ShapeDtypeStruct, no allocation), prove it
fits (memory_analysis) and extract the roofline terms (cost_analysis +
optimized-HLO collective bytes).

    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek_67b \
        --shape decode_32k --mesh single --sparsity 0.5
"""
import argparse
import functools
import json
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, runnable_cells
from repro.obs.clock import now
from repro.core import sp_schema
from repro.sparsity import SparsityPolicy
from repro.distributed.sharding import (LOGICAL_RULES_SERVE,
                                        LOGICAL_RULES_TRAIN, param_shardings,
                                        sharding_context)
from repro.launch import constants as C, hlo_analysis, roofline as R
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.models.params import logical_axes as schema_axes
from repro.optim import adamw


def _shardings_for(axes_tree, abstract_tree, ctx):
    return param_shardings(axes_tree, abstract_tree, ctx)


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                sparsity: float = 0.0, remat: str = "dots",
                overrides=None, verbose: bool = True,
                save_hlo: str = None, aligned: bool = True,
                donate_cache: bool = True):
    """Lower+compile one cell.  Returns a result record (dict)."""
    t0 = now()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    rules = LOGICAL_RULES_TRAIN if shape.mode == "train" else LOGICAL_RULES_SERVE
    sparse = sparsity > 0.0 and shape.mode != "train"

    with sharding_context(mesh, rules, overrides) as ctx:
        abstract, axes, schema = api.abstract_model(cfg)
        p_sh = _shardings_for(axes, abstract, ctx)
        in_specs = api.input_specs(cfg, shape)
        in_axes = api.input_axes(cfg, shape)
        b_sh = _shardings_for(in_axes, in_specs, ctx)
        policy = SparsityPolicy.uniform(
            "topk_shared", k_max_frac=max(1.0 - sparsity, 1e-6)) \
            if sparse else SparsityPolicy.dense()
        step, kind = api.step_for_shape(
            cfg, shape, remat=remat, policy=policy,
            aligned=aligned and shape.mode == "decode")

        args, shardings, donate = [abstract], [p_sh], ()
        if shape.mode == "train":
            opt_abs = jax.eval_shape(
                functools.partial(adamw.init, cfg=adamw.AdamWConfig()), abstract)
            opt_axes = {"m": axes, "v": axes, "master": axes, "step": ()}
            o_sh = _shardings_for(opt_axes, opt_abs, ctx)
            args += [opt_abs, in_specs]
            shardings += [o_sh, b_sh]
            donate = (0, 1)
        else:
            args += [in_specs]
            shardings += [b_sh]
            if shape.mode == "decode" and donate_cache:
                donate = (1,)          # in-place KV-cache update

        if sparse:
            sp_abs, sp_axes = sp_schema.abstract_sp(cfg)
            sp_sh = _shardings_for(sp_axes, sp_abs, ctx)
            args += [sp_abs]
            shardings += [sp_sh]

        jitted = jax.jit(step, in_shardings=tuple(shardings),
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    xla_flops, xla_bytes = R.executable_costs(compiled)
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    # trip-count-aware analysis (XLA's cost_analysis visits loop bodies once)
    ana = hlo_analysis.analyze(hlo)
    coll = ana["collectives"]
    chips = int(np.prod(mesh.devices.shape))
    rl = R.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=float(ana["flops"]),
        hlo_bytes=float(ana["bytes"]),
        coll_bytes=R.wire_bytes(coll),
        model_flops_total=R.model_flops(cfg, shape),
        # the host devices stand in for the v5e production mesh
        device_kind=C.V5E,
    )
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "chips": chips, "device_kind": rl.device_kind,
        "sparsity": sparsity if sparse else 0.0,
        "remat": remat if shape.mode == "train" else None,
        "overrides": {k: list(map(list, v)) for k, v in (overrides or {}).items()},
        "status": "ok",
        "compile_s": round(now() - t0, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            # per-device peak from XLA buffer assignment (includes arguments)
            "peak_bytes_estimate": int(getattr(mem, "peak_memory_in_bytes", 0)),
        },
        "cost": {"flops_per_device": rl.hlo_flops,
                 "bytes_per_device": rl.hlo_bytes,
                 # XLA's own numbers (loop bodies counted once) for x-check
                 "xla_flops": xla_flops,
                 "xla_bytes": xla_bytes},
        "collectives": coll,
        "roofline": rl.row(),
    }
    if verbose:
        mb = rec["memory"]["peak_bytes_estimate"] / 2**30
        print(f"[{arch} x {shape_name} x {mesh_name}"
              f"{' sparse@%.2f' % sparsity if sparse else ''}] "
              f"compile={rec['compile_s']}s peak={mb:.2f}GiB/chip "
              f"compute={rl.compute_s*1e3:.2f}ms memory={rl.memory_s*1e3:.2f}ms "
              f"coll={rl.collective_s*1e3:.2f}ms -> {rl.bottleneck} "
              f"(useful={rl.useful_flops_ratio:.2f} mfu={rl.mfu:.3f})",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) cell")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--no-aligned", dest="aligned", action="store_false",
                    help="per-sequence decode positions (scatter cache path)")
    ap.add_argument("--no-donate", dest="donate", action="store_false")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    if args.all:
        cells, skips = runnable_cells()
        for arch, shp, why in skips:
            print(f"SKIP {arch} x {shp}: {why}", flush=True)
    else:
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"],
                              r.get("sparsity", 0.0)))
                except Exception:
                    pass

    failures = 0
    for arch, shp in cells:
        for mp in meshes:
            mname = "multi" if mp else "single"
            key = (arch, shp, mname, args.sparsity
                   if SHAPES[shp].mode != "train" else 0.0)
            if key in done:
                print(f"skip (done): {key}", flush=True)
                continue
            try:
                rec = dryrun_cell(arch, shp, multi_pod=mp,
                                  sparsity=args.sparsity, remat=args.remat,
                                  save_hlo=args.save_hlo,
                                  aligned=args.aligned,
                                  donate_cache=args.donate)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shp, "mesh": mname,
                       "sparsity": args.sparsity, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"[{arch} x {shp} x {mname}] FAILED: {e}", flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
