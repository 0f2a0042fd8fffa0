_DOC = """Perf hillclimbing driver (§Perf iteration loop).

Re-derives the roofline terms for one (arch × shape) cell under config
overrides, so each hypothesis→change→measure iteration is one command:

  PYTHONPATH=src python -m repro.launch.perf --arch llama3-405b \
      --shape train_4k --tag mb4 --set microbatches=4 [--mem]

Writes reports/perf/<arch>__<shape>__<tag>.json and prints the terms.
"""

import argparse
import dataclasses
import json
import os
import time

import jax

from repro.configs import base as CB
from repro.launch import roofline as RL
from repro.launch.dryrun import _FORCE_DEVICES, build_cell
from repro.launch.mesh import make_production_mesh
from repro.models import sharding


def parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    if v in ("true", "false"):
        return k, v == "true"
    return k, v


def run(arch, shape_name, overrides, tag, do_mem, multi_pod=False):
    cfg = CB.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **dict(overrides))
    shape = CB.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = sharding.mesh_axes(mesh)

    t0 = time.time()
    cost = RL.extract_cost(cfg, shape, mesh, axes)
    mf = RL.model_flops(cfg, shape, axes["ntp"])
    rl = RL.roofline(cost, mesh.size)
    rec = dict(arch=arch, shape=shape_name, tag=tag,
               overrides=dict(overrides), **rl,
               flops=cost["flops"], hbm_bytes=cost["bytes"],
               coll_bytes=cost["coll_bytes"], coll=cost["coll"],
               useful_ratio=(mf / mesh.size) / max(cost["flops"], 1.0),
               mfu_bound=(mf / mesh.size / RL.PEAK_FLOPS)
               / max(rl["t_step"], 1e-12))
    if do_mem:
        fn, in_sh, args, donate = build_cell(cfg, shape, mesh, axes)
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh,
                               donate_argnums=donate).lower(*args).compile()
        ma = compiled.memory_analysis()
        rec["peak_gib"] = round((ma.argument_size_in_bytes
                                 + ma.temp_size_in_bytes) / 2**30, 2)
    rec["wall_s"] = round(time.time() - t0, 1)
    os.makedirs("reports/perf", exist_ok=True)
    with open(f"reports/perf/{arch}__{shape_name}__{tag}.json", "w") as f:
        json.dump(rec, f, indent=1, default=str)
    print(f"{arch} {shape_name} [{tag}] bound={rec['bound']} "
          f"t_comp={rec['t_compute']*1e3:.1f}ms t_mem={rec['t_memory']*1e3:.1f}ms "
          f"t_coll={rec['t_collective']*1e3:.1f}ms mfu={rec['mfu_bound']:.3f} "
          + (f"peak={rec.get('peak_gib')}GiB" if do_mem else ""))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--mem", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    run(args.arch, args.shape, [parse_override(s) for s in args.set],
        args.tag, args.mem, args.multi_pod)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = _FORCE_DEVICES
    main()
