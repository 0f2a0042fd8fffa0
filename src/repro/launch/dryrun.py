_DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  * params/opt/caches enter as ShapeDtypeStruct (zero allocation);
  * jit(step).lower(...).compile() against the production mesh —
    16×16 single-pod and 2×16×16 multi-pod;
  * record memory_analysis() (per-device bytes — proves fit),
    cost_analysis(), the collective schedule parsed from the compiled
    module, and (optionally) the composed roofline cost terms;
  * write reports/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--roofline]
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from functools import partial

import jax

from repro.configs import base as CB
from repro.launch import roofline as RL
from repro.launch import specs as SPECS
from repro.launch.mesh import make_production_mesh
from repro.models import lm, sharding, steps

# the production mesh is 512 virtual host devices; XLA reads the count
# when the backend starts, so the flag is set before anything touches it
_FORCE_DEVICES = "--xla_force_host_platform_device_count=512"


def build_cell(cfg, shape, mesh, axes):
    """(fn, in_shardings, args) for the FULL-config compile (scan layers)."""
    params = jax.eval_shape(
        partial(lm.init_params, cfg, model_shards=axes["ntp"]),
        jax.random.PRNGKey(0))
    psp = sharding.to_named(sharding.param_specs(cfg, params, axes), mesh)
    if shape.kind == "train":
        opt = jax.eval_shape(partial(steps.init_opt, cfg), params)
        osp = dict(m=psp, v=psp, count=sharding.to_named(
            jax.sharding.PartitionSpec(), mesh))
        batch = SPECS.batch_specs_for(cfg, shape)
        bsp = sharding.to_named(sharding.batch_specs(cfg, batch, axes), mesh)
        fn = steps.make_train_step(cfg, mesh, axes)
        return (fn, (psp, osp, bsp), (params, opt, batch), (0, 1))
    if shape.kind == "prefill":
        batch = SPECS.prefill_specs_for(cfg, shape)
        bsp = sharding.to_named(sharding.batch_specs(cfg, batch, axes), mesh)
        fn = steps.make_prefill(cfg, mesh, axes)
        return (fn, (psp, bsp), (params, batch), ())
    cache, tokens = SPECS.decode_specs_for(cfg, shape)
    csp = sharding.to_named(sharding.cache_specs(cfg, cache, axes), mesh)
    tsp = sharding.to_named(
        sharding.batch_specs(cfg, {"tokens": tokens}, axes), mesh)["tokens"]
    fn = steps.make_decode_step(cfg, mesh, axes)
    return (fn, (psp, csp, tsp), (params, cache, tokens), (1,))


def run_cell(arch: str, shape_name: str, mesh, *, do_roofline: bool,
             outdir: str, mesh_tag: str) -> dict:
    cfg = CB.get(arch)
    shape = CB.SHAPES[shape_name]
    ok, why = CB.runnable(cfg, shape)
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_tag, skipped=not ok,
               skip_reason=why)
    if ok:
        axes = sharding.mesh_axes(mesh)
        t0 = time.time()
        fn, in_sh, args, donate = build_cell(cfg, shape, mesh, axes)
        with jax.sharding.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        nchips = mesh.size
        rec |= dict(
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            device_bytes=dict(
                argument=ma.argument_size_in_bytes,
                output=ma.output_size_in_bytes,
                temp=ma.temp_size_in_bytes,
                alias=ma.alias_size_in_bytes,
                peak_gib=round((ma.argument_size_in_bytes
                                + ma.temp_size_in_bytes
                                + ma.output_size_in_bytes
                                - ma.alias_size_in_bytes) / 2**30, 3)),
            cost_analysis=dict(
                flops=float(ca.get("flops", 0.0)),
                bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                note="per-device post-SPMD; scan bodies counted once "
                     "(see roofline for composed totals)"),
            collectives_in_module=RL.collective_bytes(txt),
            collective_schedule_head=RL.collective_schedule(txt, 40),
            nchips=nchips,
        )
        if do_roofline:
            cost = RL.extract_cost(cfg, shape, mesh, axes)
            mf = RL.model_flops(cfg, shape, axes["ntp"])
            total_p, active_p = RL.param_counts(cfg, axes["ntp"])
            rl = RL.roofline(cost, nchips)
            rec |= dict(
                roofline=dict(
                    **rl,
                    hlo_flops_per_chip=cost["flops"],
                    hbm_bytes_per_chip=cost["bytes"],
                    hbm_bytes_xla_upper=cost.get("bytes_xla_upper"),
                    coll_bytes_raw=cost.get("coll_bytes_raw"),
                    coll_bytes_per_chip=cost["coll_bytes"],
                    coll_by_kind=cost["coll"],
                    model_flops_global=mf,
                    params_total=total_p, params_active=active_p,
                    useful_ratio=(mf / nchips) / max(cost["flops"], 1.0),
                    mfu_bound=(mf / nchips / RL.PEAK_FLOPS) / max(rl["t_step"], 1e-12),
                ))
    os.makedirs(f"{outdir}/{mesh_tag}", exist_ok=True)
    path = f"{outdir}/{mesh_tag}/{arch}__{shape_name}.json"
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    cells = (CB.cells(include_skips=True) if args.all
             else [(args.arch, args.shape, *CB.runnable(
                 CB.get(args.arch), CB.SHAPES[args.shape]))])

    for (arch, shape_name, ok, why) in cells:
        t0 = time.time()
        try:
            rec = run_cell(arch, shape_name, mesh, do_roofline=args.roofline,
                           outdir=args.out, mesh_tag=mesh_tag)
            if rec.get("skipped"):
                print(f"SKIP {arch:24s} {shape_name:12s} {why}")
            else:
                r = rec.get("roofline", {})
                print(f"OK   {arch:24s} {shape_name:12s} "
                      f"peak={rec['device_bytes']['peak_gib']:7.2f}GiB "
                      f"compile={rec['compile_s']:6.1f}s "
                      + (f"bound={r.get('bound', '')}" if r else ""),
                      flush=True)
        except Exception as e:
            print(f"FAIL {arch:24s} {shape_name:12s} {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = _FORCE_DEVICES
    main()
