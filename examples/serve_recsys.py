"""Recommendation serving through `repro.serve`: train LSH-MF, build the
bucketed LSH index from the training signatures, then serve top-N requests
with candidate-only scoring — and fold an online update (paper Alg. 4) into
the running service without rebuilding the index.

    PYTHONPATH=src python examples/serve_recsys.py

With ``--online-loop`` the example instead runs the always-on supervisor
(ISSUE 10): a drifting rating stream in, recommendations out, training
micro-epochs interleaved with serving on one device budget.  Interrupt it
(ctrl-C) and run the same command again — the loop resumes from its
crash-safe checkpoint + WAL under ``--root``, exactly where it left off:

    PYTHONPATH=src python examples/serve_recsys.py --online-loop
    ^C
    PYTHONPATH=src python examples/serve_recsys.py --online-loop   # resumes
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import online, simlsh, topk
from repro.core.simlsh import SimLSHConfig
from repro.data import synthetic as syn
from repro.data.sparse import from_coo, train_test_split
from repro.serve import RecsysService, ServeConfig, build_index
from repro.train.trainer import FitConfig, fit


def main():
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=3000, N=500,
                               nnz=150_000)
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    lsh = SimLSHConfig(G=8, p=1, q=10)
    cfg = FitConfig(F=32, K=16, epochs=6, method="simlsh", lsh=lsh,
                    eval_every=6)
    res = fit(tr, te, (spec.M, spec.N), cfg, log=print)

    # ---- build the serving stack from the training byproducts ----
    sp = from_coo(*tr, (spec.M, spec.N))
    sigs = simlsh.pack_bits(res.S >= 0)          # re-sign the Alg.4 cache
    index = build_index(sigs, tail_cap=256)
    scfg = ServeConfig(topn=10, micro_batch=256, C=128, n_seeds=8, cap=8,
                       n_popular=32)
    svc = RecsysService(res.params, index, sp, scfg, JK=res.JK).warmup()

    # ---- serve a request stream ----
    rng = np.random.default_rng(1)
    for _ in range(20):
        svc.submit(rng.integers(0, spec.M, 256).astype(np.int32))
    svc.flush()
    st = svc.stats()
    print(f"candidate serving: {st['users']} users in {st['batches']} "
          f"batches → {st['qps']:,.0f} users/s (p50 {st['p50_ms']:.1f} ms)")

    # exactness check vs the dense full-scoring mode on one batch
    full = RecsysService(res.params, index, sp,
                         dataclasses.replace(scfg, mode="full")).warmup()
    probe = rng.integers(0, spec.M, 256).astype(np.int32)
    svc.take_results()
    svc.submit(probe); svc.flush()
    full.submit(probe); full.flush()
    got = svc.take_results()[0][2]
    want = full.take_results()[0][2]
    overlap = np.mean([len(set(got[u]) & set(want[u])) / got.shape[1]
                       for u in range(probe.shape[0])])
    print(f"recall@10 of candidate-only vs full scoring: {overlap:.3f}")
    print(f"full-scoring baseline: {full.stats()['qps']:,.0f} users/s")
    print("sample recommendations for user", int(probe[0]), ":", got[0])

    # ---- online ingestion: new users/items arrive (paper Alg. 4) ----
    st0 = online.OnlineState(params=res.params, S=res.S, JK=res.JK, sp=sp,
                             M=spec.M, N=spec.N, hash_key=res.hash_key)
    M2, N2 = spec.M + 100, spec.N + 20
    n_new = 2000
    nr = rng.integers(0, M2, n_new).astype(np.int32)
    nc = rng.integers(0, N2, n_new).astype(np.int32)
    pair = np.unique(nr.astype(np.int64) * N2 + nc)
    # ΔΩ must be disjoint from the already-observed pairs (from_coo wants
    # unique triples in the merged matrix)
    seen = np.asarray(sp.rows).astype(np.int64) * N2 + np.asarray(sp.cols)
    pair = np.setdiff1d(pair, seen, assume_unique=True)
    nr, nc = (pair // N2).astype(np.int32), (pair % N2).astype(np.int32)
    nv = rng.uniform(1, 5, nr.shape[0]).astype(np.float32)
    st1 = online.online_update(
        st0, jnp.asarray(nr), jnp.asarray(nc), jnp.asarray(nv), lsh,
        cfg.hp, jax.random.PRNGKey(7), M_new=M2, N_new=N2, K=cfg.K, epochs=2)
    svc.ingest_online_update(st1, N_old=spec.N)
    print(f"ingested ΔΩ: catalog {spec.N} → {svc.index.n_items} items "
          f"(tail occupancy {int(svc.index.tail_len)}/{svc.index.tail_cap})")

    svc.submit(rng.integers(0, M2, 256).astype(np.int32))
    svc.flush()
    items = svc.take_results()[-1][2]
    new_hits = int(((items >= spec.N) & (items != topk.SENTINEL)).sum())
    print(f"post-ingest serving OK; new items in recommendations: {new_hits}")


def _disjoint_delta(st, M_new, N_new, rng, n=400):
    """ΔΩ triples disjoint from the already-observed pairs (the merge
    wants unique triples)."""
    nr = rng.integers(0, M_new, n).astype(np.int32)
    nc = rng.integers(0, N_new, n).astype(np.int32)
    pair = np.unique(nr.astype(np.int64) * N_new + nc)
    seen = (np.asarray(st.sp.rows).astype(np.int64) * N_new
            + np.asarray(st.sp.cols))
    pair = np.setdiff1d(pair, seen, assume_unique=True)
    return ((pair // N_new).astype(np.int32),
            (pair % N_new).astype(np.int32),
            rng.uniform(1, 5, pair.shape[0]).astype(np.float32))


def online_loop_main(args):
    """The always-on loop: train once, then slice serve/train/publish
    forever-ish, crash-safe under ``args.root``.  The drift schedule is
    keyed on the loop's own slice counter, so a restart continues the
    same stream the interrupted run was on."""
    from repro.loop import LoopConfig, OnlineLoop

    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=1500, N=300,
                               nnz=60_000)
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    lsh = SimLSHConfig(G=8, p=1, q=10)
    cfg = FitConfig(F=32, K=8, epochs=3, method="simlsh", lsh=lsh,
                    eval_every=3)
    print(f"training the base model ({spec.M}×{spec.N}, "
          f"{len(tr[0]):,} ratings) …")
    res = fit(tr, te, (spec.M, spec.N), cfg, log=lambda *a, **k: None)
    sp = from_coo(*tr, (spec.M, spec.N))
    base = online.OnlineState(params=res.params, S=res.S, JK=res.JK, sp=sp,
                              M=spec.M, N=spec.N, hash_key=res.hash_key)
    scfg = ServeConfig(topn=10, micro_batch=128, C=128, n_seeds=8, cap=8,
                       n_popular=32)
    lcfg = LoopConfig(serve_flushes=2, micro_epochs=1, micro_batch=2048,
                      deltas_per_slice=2, max_lag=2, ckpt_every=2,
                      drift_every=4, tail_cap=128, seed=0)
    hold = tuple(np.asarray(a)[:500] for a in te)

    # resume if the root holds a previous run's checkpoint + WAL; the
    # deterministically re-trained `base` seeds a first run (or one
    # interrupted before its first checkpoint)
    loop = OnlineLoop.recover(args.root, lsh, cfg.hp, scfg, K=cfg.K,
                              epochs=2, batch=4096, cfg=lcfg,
                              base_state=base, holdout=hold)
    if loop.slice_count:
        print(f"resumed from {args.root}: slice {loop.slice_count}, "
              f"WAL seq {loop.updater.seq}, catalog {loop.state.N} items")
    else:
        print(f"fresh run (state under {args.root})")

    rng = np.random.default_rng(99)         # request traffic (not resumed)
    try:
        for _ in range(args.slices):
            s = loop.slice_count
            loop.svc.submit(rng.integers(0, spec.M, 128).astype(np.int32))
            if s % 2 == 0:                  # the stream grows the catalog
                drng = np.random.default_rng(1000 + s)   # keyed on slice
                M2, N2 = loop.state.M + 8, loop.state.N + 4
                nr, nc, nv = _disjoint_delta(loop.state, M2, N2, drng)
                loop.offer_delta(nr, nc, nv,
                                 np.asarray(jax.random.PRNGKey(70 + s)),
                                 M_new=M2, N_new=N2)
            loop.run_slice()
            st = loop.svc.stats()
            print(f"slice {s}: {loop.state.M}×{loop.state.N} | "
                  f"{st['users']} users served | staleness "
                  f"{loop.staleness_s():.2f}s | "
                  f"publishes {int(loop.obs.counter('loop.publishes'))} | "
                  f"drift rmse "
                  f"{loop.obs.gauge('loop.drift_rmse', float('nan')):.3f}")
            res_batch = loop.svc.take_results()
            if res_batch:
                u, _, items = res_batch[-1][:3]
                print(f"  user {int(u[0])} → {items[0]}")
    except KeyboardInterrupt:
        print(f"\ninterrupted at slice {loop.slice_count} — run the same "
              f"command again to resume (checkpoint + WAL in {args.root})")
        return
    print(f"done: {args.slices} slices, catalog "
          f"{spec.N} → {loop.state.N} items; rerun to continue, or rm -r "
          f"{args.root} to start over")


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--online-loop", action="store_true",
                    help="run the crash-safe always-on loop demo instead")
    ap.add_argument("--root", default="/tmp/repro_online_loop",
                    help="persistence root for the loop's checkpoint + WAL")
    ap.add_argument("--slices", type=int, default=10,
                    help="slices to run this invocation (loop mode)")
    a = ap.parse_args()
    online_loop_main(a) if a.online_loop else main()
