#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU: train, then serve.

    python3 chip_smoke.py [--seed 0]          # one chip
    python3 chip_smoke.py --four-chips        # the multi-chip paths only

One chip (no option):

* train — CULSH-MF (`repro.train.trainer.fit`, F=K=32, simLSH neighbours,
  the fused `culsh_sgd` kernel) for two epochs on the MovieLens-like shape
  at its published size (69,878 × 10,677, 9,900,054 ratings, 10% held
  out), made from ``--seed``.  Fails on a non-finite RMSE, if epoch 2 is
  not below epoch 1, if the compiled epoch does not run the kernel, or if
  the kernel and its jnp reference disagree on one 512-wide batch.
* serve — `repro.serve.RecsysService` on the 1M-item planted catalog
  (F=48) through the kernel walk path (`lsh_retrieve` → `candidate_score`):
  warm up, serve 32 micro-batches of 256 users, and compare recall@10 on
  256 probe users against an exact HIGHEST-precision scan, for the kernel
  path and for the jnp reference path on the same chip.  Fails on any
  fallback or degraded answer, if the flush program lacks either kernel,
  or if the kernel path's recall is more than 0.02 below the reference's.

Four chips (``--four-chips``): the sharded serving tier (``shards=4``)
against the single-device walk on the same 1M catalog (recall@10 within
±0.01), and one sharded training epoch (the ``shards=4`` shard-map tier)
against the single-device replay of the same schedule (parameters and RMSE
within 1e-5).  The epoch runs the MovieLens-like shape with its ratings
cut to 2,000,000 to keep four chips' set-up time short.

Every result goes on its own line; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any work.  Timings printed here come from a smoke run and
are not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PROBE_USERS = 256          # probe users for recall@10
TOPN = 10
SERVE_N = 1_000_000
SHARDED_EPOCH_NNZ = 2_000_000


def say(*parts) -> None:
    print(*parts, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def kernels_in(hlo: str) -> set[str]:
    """Names of the Pallas kernels a compiled program runs on the TPU."""
    return {name for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for name in re.findall(r"jit\((\w+)\)/pallas_call", line)}


def exact_topn(params, users, topn: int, chunk: int = 32):
    """Top-n item ids over the whole catalog at full f32 precision (the
    TPU's default matmul precision rounds operands to bf16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def top(u):
        s = (params.mu + params.b[u][:, None] + params.bh[None, :]
             + jnp.dot(params.U[u], params.V.T,
                       precision=jax.lax.Precision.HIGHEST))
        return jax.lax.top_k(s, topn)[1]

    return np.concatenate([np.asarray(top(users[i:i + chunk]))
                           for i in range(0, users.shape[0], chunk)])


def overlap(got, exact) -> float:
    hits = sum(len(set(got[u].tolist()) & set(exact[u].tolist()))
               for u in range(exact.shape[0]))
    return hits / exact.size


def served_items(svc, users):
    """Items the service answers for ``users`` (one row per user)."""
    import numpy as np
    svc.take_results()
    svc.submit(np.asarray(users))
    svc.flush()
    return np.concatenate([r[2] for r in svc.take_results()])


def serve_catalog(seed: int, N: int, tail_cap: int):
    """The planted 1M-item catalog of `benchmarks/bench_serve.py` and its
    simLSH index (18-bit band signatures, as that bench uses at 1M)."""
    import jax
    from benchmarks.bench_serve import CatalogSpec, make_catalog
    from repro.core import simlsh
    from repro.serve import build_index

    t0 = time.perf_counter()
    params, sp, _ = make_catalog(CatalogSpec(N=N), seed=seed)
    t1 = time.perf_counter()
    lsh = simlsh.SimLSHConfig(G=9, p=2, q=10, band_cap=16)
    sigs = simlsh.encode(sp, lsh, jax.random.PRNGKey(seed))
    index = build_index(sigs, tail_cap=tail_cap)
    jax.block_until_ready(index.sorted_ids)
    say(f"serve.catalog N={N} M={params.U.shape[0]} F={params.U.shape[1]} "
        f"nnz={sp.nnz} catalog_s={t1 - t0:.1f} index_s="
        f"{time.perf_counter() - t1:.1f}")
    return params, sp, index


def serve_config(**kw):
    """The 1M settings of `bench_serve` (candidate budget C=768)."""
    from repro.serve import ServeConfig
    return ServeConfig(topn=TOPN, micro_batch=256, C=768, n_seeds=16, cap=8,
                       n_popular=64, tile_b=16, band_budget=768, **kw)


# ------------------------------------------------------------- one chip

def train_phase(seed: int, spec=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import synthetic as syn
    from repro.data.sparse import train_test_split
    from repro.kernels.mf_sgd.kernel import culsh_sgd_step
    from repro.kernels.mf_sgd.ref import culsh_sgd_step_ref
    from repro.train.trainer import FitConfig, fit

    spec = spec or syn.MOVIELENS_LIKE
    t0 = time.perf_counter()
    rows, cols, vals, _ = syn.generate(spec, seed=seed)
    train, test = train_test_split(np.random.default_rng(seed), rows, cols,
                                   vals, 0.1)
    say(f"train.data {spec.name} M={spec.M} N={spec.N} nnz={spec.nnz} "
        f"train={train[0].size} test={test[0].size} "
        f"data_gen_s={time.perf_counter() - t0:.1f}")

    cfg = FitConfig(F=32, K=32, method="simlsh", use_kernels=True,
                    kernel_impl="pallas", epochs=2, seed=seed)
    res = fit(train, test, (spec.M, spec.N), cfg,
              log=lambda s: say(f"train.fit {s}"))
    span = lambda name: sum(res.registry.span_durations(name))
    say(f"train.setup neighbour_s={res.neighbour_seconds:.1f} "
        f"schedule_prep_s={res.prep_seconds:.1f} (schedule "
        f"{span('train.prep.schedule'):.1f} + pack "
        f"{span('train.prep.pack'):.1f} + eval_cache "
        f"{span('train.prep.eval_cache'):.1f}) "
        f"compile_s={res.compile_seconds:.1f}")
    prev_t, rmses = 0.0, []
    for ep, t, r in res.history:
        say(f"train.epoch {ep} s={t - prev_t:.2f} rmse={r:.5f}")
        prev_t = t
        rmses.append(r)
    require(len(rmses) == 2 and all(np.isfinite(rmses)),
            f"two finite epoch RMSEs, got {rmses}")
    require(rmses[1] < rmses[0], f"epoch-2 RMSE below epoch 1: {rmses}")
    kern = kernels_in(res.epoch_program.as_text())
    say(f"train.epoch_kernels {sorted(kern)}")
    require("culsh_sgd_step" in kern, "compiled epoch runs culsh_sgd_step")

    # one real-width batch through the kernel and through its reference
    rng = np.random.default_rng(seed)
    B, F, K = 512, 32, 32
    a = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    hp = jnp.concatenate([jnp.abs(a(12)) * 0.05, a(1) * 0.1])
    args = (a(F + 1, B), a(F + 2 * K + 1, B), a(K, B), a(K, B),
            jnp.asarray(rng.integers(0, 2, (K, B)).astype(np.float32)),
            a(B), jnp.ones((B,), jnp.float32), hp)
    got = culsh_sgd_step(*args, interpret=jax.default_backend() == "cpu")
    want = culsh_sgd_step_ref(*args)
    diff = max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want))
    say(f"train.kernel_vs_ref B={B} max_abs_diff={diff:.3e}")
    require(diff <= 1e-4, f"culsh_sgd_step within 1e-4 of its ref ({diff})")


def serve_phase(seed: int, N: int = SERVE_N) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import RecsysService, full_topn

    params, sp, index = serve_catalog(seed, N, tail_cap=128)
    M = params.U.shape[0]
    cfg = serve_config(impl="pallas")
    svc = RecsysService(params, index, sp, cfg)
    t0 = time.perf_counter()
    svc.warmup()
    say(f"serve.warmup_s={time.perf_counter() - t0:.1f} "
        f"interpret={cfg.interpret_mode()}")
    kern = kernels_in(svc.flush_hlo())
    say(f"serve.flush_kernels {sorted(kern)}")
    require({"lsh_retrieve_topc", "candidate_score_topn"} <= kern,
            "flush program runs lsh_retrieve_topc and candidate_score_topn")

    rng = np.random.default_rng(seed + 1)
    for _ in range(32):
        svc.submit(rng.integers(0, M, cfg.micro_batch).astype(np.int32))
    svc.flush()
    st = svc.stats()
    say(f"serve.flushes batches={st['batches']} users={st['users']} "
        f"qps={st['qps']:.0f} p50_ms={st['p50_ms']:.2f} "
        f"p99_ms={st['p99_ms']:.2f} fallbacks={st['fallbacks']} "
        f"degraded={st['degraded']}")
    require(st["users"] == 32 * cfg.micro_batch, "every request answered")
    require(st["fallbacks"] == 0 and st["degraded"] == 0,
            "no fallback and no degraded answer")

    probe = jnp.asarray(rng.integers(0, M, PROBE_USERS), jnp.int32)
    exact = exact_topn(params, probe, TOPN)
    rec_kernel = overlap(served_items(svc, probe), exact)
    ref = RecsysService(params, index, sp,
                        dataclasses.replace(cfg, impl="ref"))
    rec_ref = overlap(served_items(ref, probe), exact)
    rec_full = overlap(np.asarray(full_topn(params, probe, topn=TOPN)[1]),
                       exact)
    say(f"serve.recall@{TOPN} kernel={rec_kernel:.4f} ref={rec_ref:.4f} "
        f"full_topn_default_precision={rec_full:.4f} "
        f"probe_users={PROBE_USERS}")
    require(ref.stats()["fallbacks"] == 0, "reference path: no fallback")
    require(svc.stats()["fallbacks"] == 0, "kernel path: no fallback")
    require(rec_kernel >= rec_ref - 0.02,
            f"kernel recall {rec_kernel:.4f} within 0.02 of ref {rec_ref:.4f}")


# ----------------------------------------------------------- four chips

def sharded_serve_phase(seed: int, N: int = SERVE_N) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import RecsysService

    params, sp, index = serve_catalog(seed, N, tail_cap=0)
    M = params.U.shape[0]
    # the single-device walk: the XLA walk each shard also runs
    base = serve_config(impl="ref")
    one = RecsysService(params, index, sp, base)
    four = RecsysService(params, index, sp,
                         dataclasses.replace(base, shards=4))
    require(four.stats()["shards"] == 4, "sharded service holds 4 shards")
    rng = np.random.default_rng(seed + 1)
    probe = jnp.asarray(rng.integers(0, M, PROBE_USERS), jnp.int32)
    exact = exact_topn(params, probe, TOPN)
    recalls = {}
    for name, svc in (("single", one), ("sharded", four)):
        t0 = time.perf_counter()
        svc.warmup()
        warm = time.perf_counter() - t0
        for _ in range(8):
            svc.submit(rng.integers(0, M, base.micro_batch).astype(np.int32))
        svc.flush()
        recalls[name] = overlap(served_items(svc, probe), exact)
        st = svc.stats()
        say(f"sharded_serve.{name} shards={st['shards']} warmup_s={warm:.1f} "
            f"qps={st['qps']:.0f} p50_ms={st['p50_ms']:.2f} "
            f"fallbacks={st['fallbacks']} recall@{TOPN}={recalls[name]:.4f}")
        require(st["fallbacks"] == 0 and st["degraded"] == 0,
                f"{name}: no fallback and no degraded answer")
    delta = recalls["sharded"] - recalls["single"]
    say(f"sharded_serve.recall_delta={delta:+.4f}")
    require(abs(delta) <= 0.01, f"sharded recall within ±0.01 ({delta:+.4f})")


def sharded_epoch_phase(seed: int, spec=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import model, sgd
    from repro.data import synthetic as syn
    from repro.data.sparse import (conflict_free_schedule, from_coo,
                                   train_test_split)
    from repro.launch.mesh import make_shard_mesh

    spec = spec or dataclasses.replace(syn.MOVIELENS_LIKE,
                                       nnz=SHARDED_EPOCH_NNZ)
    D, F, K = 4, 32, 32
    t0 = time.perf_counter()
    rows, cols, vals, _ = syn.generate(spec, seed=seed)
    (tr_r, tr_c, tr_v), (te_r, te_c, te_v) = train_test_split(
        np.random.default_rng(seed), rows, cols, vals, 0.1)
    sp = from_coo(tr_r, tr_c, tr_v, (spec.M, spec.N))
    rng = np.random.default_rng(seed)
    JK = jnp.asarray(rng.integers(0, spec.N, (spec.N, K)), jnp.int32)
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=512, M=spec.M, N=spec.N, shards=D,
                                   seed=seed)
    sd = model.build_scheduled_data(sp, JK, sched)
    shd = model.build_shard_data(sp, JK, sched)
    require(shd is not None and sched.shard_starts.size > 0,
            "schedule has a shard tier")
    p0 = model.init_from_data(jax.random.PRNGKey(seed), sp, F, K)
    pp0 = model.pack_params(model.remap_params(p0, sched))
    jax.block_until_ready(sd.r)
    say(f"sharded_epoch.setup M={spec.M} N={spec.N} nnz={spec.nnz} "
        f"shard_frac={sched.stats()['shard']['n'] / sp.nnz:.3f} "
        f"setup_s={time.perf_counter() - t0:.1f}")

    hp = sgd.Hyper()
    key, ep = jax.random.PRNGKey(seed + 1), jnp.asarray(0)
    out = {}
    for name, mesh in (("replay", None), ("sharded", make_shard_mesh(D))):
        pp = jax.tree.map(jnp.copy, pp0)
        t0 = time.perf_counter()
        pp = sgd.train_epoch_scheduled(pp, sd, sched, key, ep, hp, shd=shd,
                                       mesh=mesh)
        jax.block_until_ready(pp.row)
        out[name] = model.unmap_params(model.unpack_params(pp), sched)
        say(f"sharded_epoch.{name} first_call_s="
            f"{time.perf_counter() - t0:.1f}")
    worst = max(float(jnp.max(jnp.abs(getattr(out["replay"], f)
                                      - getattr(out["sharded"], f))))
                for f in ("U", "V", "b", "bh", "W", "C"))
    te = [jnp.asarray(a) for a in (te_r, te_c, te_v)]
    rmse = {k: float(model.rmse(p, sp, JK, *te)) for k, p in out.items()}
    say(f"sharded_epoch.parity max_abs_param_diff={worst:.3e} "
        f"rmse_replay={rmse['replay']:.6f} rmse_sharded={rmse['sharded']:.6f}")
    require(worst <= 1e-5, f"sharded params within 1e-5 of replay ({worst})")
    require(abs(rmse["replay"] - rmse["sharded"]) <= 1e-5,
            "sharded RMSE within 1e-5 of replay")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serve and sharded epoch "
                         "paths, on four chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    say(f"devices {devices}")
    say(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if d0.platform != "tpu":
        say("no TPU found: this smoke run needs the chip")
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        say(f"needs {need} chips, found {len(devices)}")
        return 2

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro import compile_cache
    say(f"compile cache {compile_cache.enable()}")
    phases = ([sharded_serve_phase, sharded_epoch_phase] if args.four_chips
              else [train_phase, serve_phase])
    for phase in phases:
        t0 = time.perf_counter()
        say(f"== {phase.__name__}")
        phase(args.seed)
        say(f"== {phase.__name__} done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
