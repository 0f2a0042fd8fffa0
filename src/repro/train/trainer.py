"""End-to-end trainer for LSH-MF / CULSH-MF (single host or multi-device).

Wires the pipeline of paper Fig. 2:
  R (COO) → neighbour search (simLSH | GSM | RP_cos | minHash | rand)
          → J^K → fused Eq.(5) SGD epochs → RMSE eval,
with checkpoint/restart fault tolerance and optional multi-device rotation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import baselines as bl
from repro.core import gsm, model, sgd, simlsh, topk
from repro.data.sparse import SparseMatrix, conflict_free_schedule, from_coo
from repro.kernels.mf_sgd.ops import resolve_impl
from repro.launch.mesh import make_shard_mesh
from repro.train import checkpoint as ckpt


@dataclasses.dataclass
class FitConfig:
    F: int = 32
    K: int = 32
    epochs: int = 12
    batch: int = 4096
    method: str = "simlsh"      # simlsh | gsm | rand | rp_cos | minhash | none(mf)
    lsh: simlsh.SimLSHConfig = dataclasses.field(default_factory=simlsh.SimLSHConfig)
    hp: sgd.Hyper = dataclasses.field(default_factory=sgd.Hyper)
    seed: int = 0
    ckpt_dir: str | None = None
    ckpt_every: int = 0          # epochs; 0 = off
    eval_every: int = 1
    loss: str = "l2"             # l2 | bce (implicit feedback, paper §5.4)
    schedule: str = "auto"       # auto | conflict_free | none — 'none' is the
                                 # legacy per-batch-search path (bench
                                 # baseline); 'auto' currently == conflict_free
                                 # (reserved for a backend/shape heuristic)
    cf_batch: int = 512          # conflict-free batch width (≤ min(M, N) useful)
    tiers: int = 4               # schedule width tiers (full/half/…) — a
                                 # modest default; reaching cf_frac ≥ 0.85 on
                                 # heavy zipf tails takes deeper tuned ladders
                                 # (7–9 tiers at tier_shrink≈0.71 — see
                                 # benchmarks/bench_train.py SCALES)
    tier_shrink: float = 0.5     # tier width ratio; ~0.71 packs rounds ≥71%
                                 # full at the cost of more tiers/scans
    min_fill_frac: float = 0.5   # last-tier re-pack threshold
    shards: int | str = "auto"   # block-aligned shard-map tier width: 'auto'
                                 # = jax.device_count() (single-device path
                                 # when 1), or an explicit device count
    use_kernels: bool = False    # route conflict-free batches through the
                                 # fused kernels/mf_sgd training step
    kernel_impl: str = "auto"    # auto | pallas | ref — 'auto' picks the
                                 # pure-jnp ref on CPU (Pallas only
                                 # interprets there), the kernel elsewhere


@dataclasses.dataclass
class FitResult:
    params: model.Params
    JK: jax.Array | None
    history: list            # [(epoch, seconds, rmse)] — seconds are the
                             # accumulated `train.epoch` span times from
                             # the obs registry, excluding jit compilation
                             # (see compile_seconds)
    neighbour_seconds: float
    S: jax.Array | None = None  # simLSH accumulators (online cache)
    hash_key: jax.Array | None = None  # key S was encoded with (Alg. 4 needs
                                       # the same Φ family for ΔΩ)
    compile_seconds: float = 0.0  # AOT epoch-fn compile (one-off)
    prep_seconds: float = 0.0     # gather cache + conflict-free schedule
    schedule_stats: dict | None = None
    registry: obs.Registry | None = None  # the registry every timing above
                                          # was read from (ISSUE 6)
    epoch_program: object = None  # the AOT-compiled epoch (`as_text()`
                                  # shows which kernels it runs)


def build_neighbours(sp: SparseMatrix, cfg: FitConfig, key,
                     registry: obs.Registry | None = None):
    """Neighbour search stage — (JK or None, seconds, S or None, sig key).
    simLSH's two parts are timed in ``registry``'s spans
    ``train.neighbours.encode`` and ``train.neighbours.topk``."""
    reg = registry if registry is not None else obs.get()
    t0 = time.perf_counter()
    S = None
    k_sig, k_top = jax.random.split(key)
    if cfg.method == "none":
        return None, 0.0, None, k_sig
    if cfg.method == "simlsh":
        with reg.span("train.neighbours.encode"):
            sigs, S = simlsh.encode(sp, cfg.lsh, k_sig,
                                    return_accumulators=True)
            jax.block_until_ready(sigs)
        with reg.span("train.neighbours.topk"):
            JK = jax.block_until_ready(topk.topk_from_signatures(
                sigs, k_top, K=cfg.K, band_cap=cfg.lsh.band_cap))
    elif cfg.method == "gsm":
        JK = gsm.gsm_topk(sp, K=cfg.K)
    elif cfg.method == "rand":
        JK = bl.rand_topk(k_top, sp.N, cfg.K)
    elif cfg.method == "rp_cos":
        sigs = bl.rp_cos_signatures(sp, cfg.lsh, k_sig)
        JK = bl.signatures_topk(sigs, k_top, K=cfg.K, band_cap=cfg.lsh.band_cap)
    elif cfg.method == "minhash":
        sigs = bl.minhash_signatures(sp, cfg.lsh, k_sig)
        JK = bl.signatures_topk(sigs, k_top, K=cfg.K, band_cap=cfg.lsh.band_cap)
    else:
        raise ValueError(f"unknown method {cfg.method}")
    JK = jax.block_until_ready(JK)
    return JK, time.perf_counter() - t0, S, k_sig


def fit(train_coo, test_coo, shape, cfg: FitConfig,
        log: Callable[[str], None] | None = None,
        registry: obs.Registry | None = None) -> FitResult:
    # all fit timings live in one obs registry (ISSUE 6) — the shared
    # process registry when enabled (so train spans land on the unified
    # timeline next to serve/online ones), else a private enabled one so
    # FitResult timing always works.  Every FitResult timing field below
    # is *read back* from the registry's spans, never from a second
    # stopwatch.
    reg = registry if registry is not None else obs.scoped()
    key = jax.random.PRNGKey(cfg.seed)
    k_nb, k_init, k_ep = jax.random.split(key, 3)
    sp = from_coo(*train_coo, shape)
    te_r, te_c, te_v = (jnp.asarray(a) for a in test_coo)
    # steps for the ratings' own scale (`sgd.Hyper`): cfg.hp on 1–5 data
    scale = sgd.rating_scale(sp.vals)
    hp = cfg.hp.for_rating_scale(scale)
    reg.counter_add("train.rating_scale", scale)
    if log:
        log(f"rating scale: s = {scale} (steps a_u, a_v / s, a_w / s^2)")

    with reg.span("train.neighbours"):
        JK, _, S, k_sig = build_neighbours(sp, cfg, k_nb, reg)
    nb_secs = reg.span_durations("train.neighbours")[-1]
    mf_only = cfg.method == "none"
    if JK is None:  # plain MF still needs a JK placeholder for batch assembly
        JK = jnp.zeros((sp.N, cfg.K), jnp.int32)

    params = model.init_from_data(k_init, sp, cfg.F, cfg.K)

    start_epoch = 0
    if cfg.ckpt_dir:
        restored = ckpt.try_restore(cfg.ckpt_dir, params)
        if restored is not None:
            params, start_epoch = restored

    if cfg.schedule not in ("auto", "conflict_free", "none"):
        raise ValueError(f"unknown schedule {cfg.schedule}")
    scheduled = cfg.schedule != "none"
    bce = cfg.loss == "bce"

    # shard resolution: block-aligned shard-map tier only when the host
    # actually has multiple devices (single-device path otherwise)
    shards = jax.device_count() if cfg.shards == "auto" else int(cfg.shards)
    shards = max(1, min(shards, jax.device_count(), sp.M, sp.N))
    mesh = make_shard_mesh(shards) if scheduled and shards > 1 else None

    # once-per-fit precomputation: tiered conflict-free schedule + the
    # schedule-ordered training data (+ dense shard-tier cells) + eval
    # gather cache (Ω, J^K and the test set are fixed for the whole
    # offline fit).  Prep is a one-off cost amortized over epochs —
    # schedule_stats reports both.
    prep_secs = 0.0
    sched_stats = None
    ec = None
    shd = None
    if scheduled:
        with reg.span("train.prep"):
            with reg.span("train.prep.schedule"):
                sched = conflict_free_schedule(
                    np.asarray(sp.rows), np.asarray(sp.cols),
                    batch=min(cfg.cf_batch, cfg.batch), tiers=cfg.tiers,
                    tier_shrink=cfg.tier_shrink,
                    min_fill_frac=cfg.min_fill_frac,
                    shards=shards, M=sp.M, N=sp.N, seed=cfg.seed)
            with reg.span("train.prep.pack"):
                sd = model.build_scheduled_data(sp, JK, sched,
                                                mf_only=mf_only)
                shd = model.build_shard_data(sp, JK, sched, mf_only=mf_only)
            if cfg.eval_every:
                with reg.span("train.prep.eval_cache"):
                    ec = model.build_eval_cache(sp, JK, te_r, te_c,
                                                mf_only=mf_only)
            jax.block_until_ready(sd.r)
        prep_secs = reg.span_durations("train.prep")[-1]
        sched_stats = dict(
            sched.stats(), prep_sec=prep_secs,
            prep_per_epoch=prep_secs / max(cfg.epochs - start_epoch, 1))
        if log:
            log(f"schedule: {sched_stats['nb_cf']} cf + "
                f"{sched_stats['nb_lo']} leftover batches "
                f"(cf_frac={sched_stats['cf_frac']:.2f}, "
                f"fill={sched_stats['fill']:.2f}, prep={prep_secs:.2f}s "
                f"= {sched_stats['prep_per_epoch']:.3f}s/epoch)")

    # impl resolution needs the backend, so it happens here, outside jit
    # (mirrors the candidate_score impl="auto" pattern)
    impl = resolve_impl(cfg.kernel_impl) if cfg.use_kernels else "ref"
    interpret = jax.default_backend() == "cpu"

    # AOT-compile the epoch fn so jit compilation is charged to
    # compile_seconds, never to history / benchmark training time — the
    # `train.compile` span keeps the compile/steady-state separation
    # visible in the trace, too
    with reg.span("train.compile"):
        ep0 = jnp.asarray(start_epoch)
        k0 = jax.random.fold_in(k_ep, start_epoch)
        if scheduled:
            # training state: block-padded id space (shard schedules relay
            # every id through sched.row_map/col_map) + the two packed
            # planes; unpacked original-id Params only at the
            # eval/ckpt/result boundary
            state = model.pack_params(model.remap_params(params, sched))
            to_public = lambda q: model.unmap_params(model.unpack_params(q),
                                                     sched)
            epoch_fn = sgd.train_epoch_scheduled.lower(
                state, sd, sched, k0, ep0, hp, shd=shd, mf_only=mf_only,
                bce=bce, use_kernels=cfg.use_kernels, impl=impl,
                interpret=interpret, mesh=mesh).compile()
            run = lambda qq, kk, ee: epoch_fn(qq, sd, sched, kk, ee, hp,
                                              shd=shd)
            nb_kw = dict(mf_only=mf_only, platform=jax.default_backend())
            nb_bias_steps = sgd.nb_bias_lookup_steps(
                sched, state.col.shape[0], state.K, **nb_kw)
            nb_gather_steps = sgd.nb_bias_gather_steps(
                sched, state.col.shape[0], state.K, **nb_kw)
        else:
            state = params
            to_public = lambda q: q
            epoch_fn = sgd.train_epoch.lower(
                state, sp, JK, k0, ep0, hp, batch=cfg.batch,
                mf_only=mf_only, bce=bce).compile()
            run = lambda qq, kk, ee: epoch_fn(qq, sp, JK, kk, ee, hp)
            nb_bias_steps = 0
            nb_gather_steps = 0 if mf_only else -(-sp.nnz // cfg.batch)
    compile_secs = reg.span_durations("train.compile")[-1]

    history = []
    t_train = 0.0
    for ep in range(start_epoch, cfg.epochs):
        with reg.span("train.epoch"):
            state = run(state, jax.random.fold_in(k_ep, ep), jnp.asarray(ep))
            jax.block_until_ready(jax.tree.leaves(state)[0])
        t_train += reg.span_durations("train.epoch")[-1]
        reg.counter_add("train.epochs")
        # steps whose neighbour b̂ took the vectorised lookup, and the rest
        reg.counter_add("train.nb_bias_lookup_steps", nb_bias_steps)
        reg.counter_add("train.nb_bias_gather_steps", nb_gather_steps)
        if cfg.eval_every and (ep + 1) % cfg.eval_every == 0:
            with reg.span("train.epoch.eval"):
                p_eval = to_public(state)
                if ec is not None:  # per-epoch eval is a cached gather scan
                    r = float(model.rmse_cached(p_eval, ec, te_r, te_c, te_v,
                                                mf_only=mf_only))
                else:
                    r = float(model.rmse(p_eval, sp, JK, te_r, te_c, te_v,
                                         mf_only=mf_only))
            history.append((ep, t_train, r))
            reg.event("train.eval", epoch=ep, t_train=t_train, rmse=r)
            if log:
                log(f"epoch {ep:3d}  t={t_train:7.2f}s  rmse={r:.4f}")
        if cfg.ckpt_dir and cfg.ckpt_every and (ep + 1) % cfg.ckpt_every == 0:
            with reg.span("train.ckpt"):
                ckpt.save(cfg.ckpt_dir, to_public(state), step=ep + 1)

    params = to_public(state)
    return FitResult(params, JK, history, nb_secs, S, hash_key=k_sig,
                     compile_seconds=compile_secs, prep_seconds=prep_secs,
                     schedule_stats=sched_stats, registry=reg,
                     epoch_program=epoch_fn)
