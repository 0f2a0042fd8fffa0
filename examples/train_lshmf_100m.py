"""End-to-end driver: train a ~100M-parameter LSH-MF model for a few
hundred steps, with checkpointing (deliverable (b): the ~100M train run).

Model size: (M + N)·F + 3·N·K + M + N ≈ 100M params at
M=700k, N=30k, F=128, K=64 — the netflix-scale geometry of the paper.
Data is a matched synthetic sparse matrix (~2M interactions here to keep
the CPU run in minutes; the trainer streams epochs of conflict-averaged
mini-batches, each jit-compiled once).

    PYTHONPATH=src python examples/train_lshmf_100m.py [--small]
        [--trace /tmp/train_trace.json]
"""
import argparse
import dataclasses
import time

import numpy as np

from repro import compile_cache, obs
from repro.core.simlsh import SimLSHConfig
from repro.data import synthetic as syn
from repro.data.sparse import train_test_split
from repro.train.trainer import FitConfig, fit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="10M-param variant (fast CI-style run)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the checkpoint dir instead of fresh")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the fit's obs spans as Chrome trace-event "
                         "JSON (load in https://ui.perfetto.dev)")
    args = ap.parse_args()

    if args.small:
        M, N, F, K, nnz, epochs = 80_000, 6_000, 64, 32, 400_000, 3
    else:
        M, N, F, K, nnz, epochs = 700_000, 30_000, 128, 64, 2_000_000, 3

    nparams = (M + N) * F + 3 * N * K + M + N
    print(f"model: M={M:,} N={N:,} F={F} K={K} → {nparams/1e6:.1f}M params")

    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=M, N=N, nnz=nnz)
    t0 = time.time()
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    print(f"data: {len(vals):,} interactions ({time.time()-t0:.1f}s)")

    steps_per_epoch = -(-len(tr[0]) // 8192)
    print(f"{epochs} epochs × {steps_per_epoch} steps "
          f"= {epochs * steps_per_epoch} optimizer steps")

    ckpt_dir = f"/tmp/lshmf_100m_ckpt_{'small' if args.small else 'full'}"
    if not args.resume:
        import shutil
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    cfg = FitConfig(
        F=F, K=K, epochs=epochs, batch=8192, method="simlsh",
        lsh=SimLSHConfig(G=8, p=1, q=10, band_cap=16),
        ckpt_dir=ckpt_dir, ckpt_every=1,
    )
    res = fit(tr, te, (M, N), cfg, log=print)
    print(f"done: rmse={res.history[-1][2]:.4f}, "
          f"neighbour stage {res.neighbour_seconds:.1f}s")

    # --- observability summary (ISSUE 6): every number below is read
    # back from the fit's obs registry — the same spans a --trace export
    # shows in Perfetto, so the printed summary and the trace can't drift
    reg = res.registry
    snap = reg.snapshot()
    print("\nobs summary (from the fit registry):")
    for name in ("train.neighbours", "train.prep", "train.compile",
                 "train.epoch", "train.epoch.eval", "train.ckpt"):
        s = snap["histograms"].get(name)
        if not s or not s["count"]:
            continue
        print(f"  {name:<18} n={s['count']:>3}  total={s['sum']:7.2f}s  "
              f"p50={s['p50'] * 1e3:8.1f}ms  p95={s['p95'] * 1e3:8.1f}ms")
    steady = reg.hist_summary("train.epoch")
    if steady["count"]:
        print(f"  steady-state epoch min={steady['min']:.3f}s "
              f"(compile {res.compile_seconds:.2f}s charged separately)")
    if args.trace:
        obs.write_trace(args.trace, reg)
        print(f"  trace → {args.trace} "
              f"({snap['spans']['retained']} spans; open in Perfetto)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
