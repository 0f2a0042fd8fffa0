"""Training-throughput benchmark: the scheduled hot path, measured.

Compares, per synthetic Zipf scale, steady-state epoch time (jit compile
excluded via AOT `.lower().compile()`; **min over epochs** — this
container has noisy neighbours that inflate individual epochs 20–100%,
and the min is the standard noise-robust estimator of achievable cost,
applied identically to every path) and updates/sec for:

  * ``base``   — legacy `sgd.train_epoch`: per-batch B×K binary-search
    assembly + per-batch collision rescaling,
  * ``sched``  — `sgd.train_epoch_scheduled`: tiered conflict-free
    schedule scanned over the schedule-ordered `ScheduledData`
    (contiguous-slice assembly; scaled fallback for the zipf-head
    residue), parameters in the packed planes (`model.PackedParams`:
    2 scatters/step vs the legacy path's 6) donated across epochs,
  * ``kernel`` — same, with the fused `kernels/mf_sgd` step on every
    conflict-free tier (``impl="auto"``: pure-jnp ref on CPU, Pallas
    elsewhere).

Also trains both paths for equal epochs from the same init and reports the
held-out RMSE of each (via the per-fit `EvalCache` gather scan), so the
speedup is shown not to cost accuracy.  Results land in
``BENCH_train.json`` at the repo root (see --out).

    PYTHONPATH=src:. python benchmarks/bench_train.py [--scales small,medium,large]
        [--epochs 5] [--smoke] [--check] [--out BENCH_train.json]

``--check`` is the CI regression gate: it asserts the BENCH_train.json
floors (tiered cf_frac ≥ 0.8 everywhere; sched ≥ 2× the legacy path at
the recorded scales, ≥ 1.5× at smoke scale — see CHECK_SPEEDUP_SMOKE)
after the run and exits non-zero on regression.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro import compile_cache, obs
from repro.core import model, sgd, simlsh, topk
from repro.data import synthetic as syn
from repro.data.sparse import conflict_free_schedule, from_coo, train_test_split
from repro.kernels.mf_sgd.ops import resolve_impl

SCALES = {
    # name: (M, N, nnz, cf_batch, tiers, tier_shrink) — zipf-tailed via
    # synthetic.generate.  Schedule knobs are the measured per-scale sweet
    # spots: tier-0 width ≈ min(M, N) (widest steps amortize the fixed
    # per-step scatter cost), a ~quarter-octave shrink (0.71) so emitted
    # rounds are ≥71% full (cf_fill ≈ 0.89 vs 0.77 with plain halving),
    # and enough tiers that the deep zipf tail stays conflict-free
    # (cf_frac ≥ 0.85) instead of spilling to the scaled path.
    "smoke": (400, 100, 6_000, 96, 6, 0.71),
    "small": (1_500, 300, 60_000, 300, 7, 0.71),
    "medium": (3_000, 500, 150_000, 512, 7, 0.71),
    "large": (8_000, 2_000, 600_000, 2_048, 9, 0.71),
}
F, K = 32, 16
BATCH = 4096          # legacy-path batch (the trainer default)
# --check floors (ISSUE 3 / CI gate).  cf_frac is deterministic per seed;
# the wall-clock floor is 2.0 at the recorded bench scales but relaxed at
# smoke scale, where the legacy path is overhead-dominated (2 batches per
# epoch) and its structural speedup sits at ~2x — a 2.0 smoke floor would
# gate CI on noisy-neighbour luck, not on regressions.
CHECK_CF_FRAC = 0.8
CHECK_SPEEDUP = 2.0
CHECK_SPEEDUP_SMOKE = 1.5


def setup(name: str, seed: int = 0):
    M, N, nnz, cf_batch, _tiers, _shrink = SCALES[name]
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=M, N=N, nnz=nnz)
    rows, cols, vals, _ = syn.generate(spec, seed=seed)
    rng = np.random.default_rng(seed)
    tr, te = train_test_split(rng, rows, cols, vals, 0.1)
    sp = from_coo(*tr, (M, N))
    key = jax.random.PRNGKey(seed)
    lsh = simlsh.SimLSHConfig(G=8, p=2, q=4, band_cap=16)
    sigs = simlsh.encode(sp, lsh, key)
    JK = topk.topk_from_signatures(sigs, jax.random.fold_in(key, 1), K=K,
                                   band_cap=lsh.band_cap)
    params = model.init_from_data(jax.random.fold_in(key, 2), sp, F, K)
    jax.block_until_ready(JK)
    return sp, JK, params, te, cf_batch, _tiers, _shrink


def run_epochs(compiled, run_args, params, epochs: int,
               reg: obs.Registry | None = None, name: str = "train.epoch"):
    """AOT-compiled epoch fn → (params, [sec/epoch]).

    With a registry, each epoch is an obs span and the reported times are
    the span durations read back from it — the bench shares the trainer's
    timing source (ISSUE 6) instead of a second stopwatch.  Without one
    (the disabled arm of the obs-overhead measurement) a plain stopwatch
    times the identical loop."""
    times = []
    for ep in range(epochs):
        if reg is not None and reg.enabled:
            with reg.span(name):
                params = compiled(params, *run_args(ep))
                jax.block_until_ready(jax.tree.leaves(params)[0])
            times.append(reg.span_durations(name)[-1])
        else:
            t0 = time.perf_counter()
            params = compiled(params, *run_args(ep))
            jax.block_until_ready(jax.tree.leaves(params)[0])
            times.append(time.perf_counter() - t0)
    return params, times


def obs_overhead(compiled, run_args, params0, epochs: int, copy) -> dict:
    """Enabled-vs-disabled obs cost on the steady-state epoch loop: same
    compiled fn, same data, the arms *interleaved* epoch by epoch so both
    sample the same noise window, with the arm order swapped every round
    (a fixed order biases whichever arm runs first into/out of noise
    bursts).  The statistic is the MEDIAN over rounds, not the min the
    rest of this bench uses: under bursty container noise the min
    decorrelates between arms (one lucky quiet window lands in a single
    arm and swings the ratio ±10–20% either way — measured), while the
    median of order-swapped interleaved rounds is a paired statistic that
    cancels the bursts.  The span-per-epoch cost is a few µs against
    ms..s epochs, so overhead_frac should sit well inside the ±2% target
    (noise can make it slightly negative)."""
    reg = obs.Registry(enabled=True)
    p_on, p_off = copy(params0), copy(params0)
    t_on, t_off = [], []

    def run_on(ep):
        nonlocal p_on
        with reg.span("train.epoch"):
            p_on = compiled(p_on, *run_args(ep))
            jax.block_until_ready(jax.tree.leaves(p_on)[0])
        t_on.append(reg.span_durations("train.epoch")[-1])

    def run_off(ep):
        nonlocal p_off
        t0 = time.perf_counter()
        p_off = compiled(p_off, *run_args(ep))
        jax.block_until_ready(jax.tree.leaves(p_off)[0])
        t_off.append(time.perf_counter() - t0)

    rounds = max(epochs, 12)
    for ep in range(rounds):
        first, second = (run_on, run_off) if ep % 2 == 0 else (run_off, run_on)
        first(ep)
        second(ep)
    on = float(np.median(t_on))
    off = float(np.median(t_off))
    return dict(enabled_sec_per_epoch=on, disabled_sec_per_epoch=off,
                overhead_frac=on / off - 1.0, rounds=rounds,
                statistic="median-over-interleaved-order-swapped-rounds")


def bench_scale(name: str, *, epochs: int, seed: int = 0,
                measure_overhead: bool = True) -> dict:
    # every timing below is an obs span read back from this registry —
    # the shared process registry when the caller enabled it (--trace),
    # else a private enabled one (obs.scoped())
    reg = obs.scoped()
    sp, JK, params0, te, cf_batch, tiers, shrink = setup(name, seed)
    te_r, te_c, te_v = (jnp.asarray(a) for a in te)
    hp = sgd.Hyper()
    k_ep = jax.random.PRNGKey(seed + 17)
    keys = lambda ep: jax.random.fold_in(k_ep, ep)
    copy = lambda p: jax.tree.map(jnp.copy, p)
    out = dict(name=name, M=sp.M, N=sp.N, nnz=sp.nnz, F=F, K=K,
               batch=BATCH, cf_batch=cf_batch, tiers=tiers,
               tier_shrink=shrink, epochs=epochs)
    ec = model.build_eval_cache(sp, JK, te_r, te_c)
    ev = lambda p: float(model.rmse_cached(p, ec, te_r, te_c, te_v))

    # --- base: legacy per-batch-search path -------------------------------
    with reg.span("train.compile.base"):
        base_fn = sgd.train_epoch.lower(
            params0, sp, JK, keys(0), jnp.asarray(0), hp,
            batch=BATCH).compile()
    p_base, times = run_epochs(
        base_fn, lambda ep: (sp, JK, keys(ep), jnp.asarray(ep), hp),
        copy(params0), epochs, reg, "train.epoch.base")
    sec = min(times)
    out["base"] = dict(sec_per_epoch=sec, updates_per_sec=sp.nnz / sec,
                       compile_sec=reg.span_durations(
                           "train.compile.base")[-1],
                       rmse=ev(p_base))
    emit(f"train.base.{name}", sec, f"ups={sp.nnz / sec:,.0f}")

    # --- tiered schedule + schedule-ordered data (± fused kernels) --------
    # the scheduled paths train on the packed planes (model.PackedParams:
    # 2 scatters/step vs 6 unpacked) and unpack only for the RMSE eval
    with reg.span("train.prep"):
        sched = conflict_free_schedule(np.asarray(sp.rows),
                                       np.asarray(sp.cols),
                                       batch=cf_batch, tiers=tiers,
                                       tier_shrink=shrink,
                                       M=sp.M, N=sp.N, seed=seed)
        sd = model.build_scheduled_data(sp, JK, sched)
        jax.block_until_ready(sd.r)
    prep = reg.span_durations("train.prep")[-1]
    out["schedule"] = dict(prep_sec=prep, prep_per_epoch=prep / epochs,
                           **sched.stats())
    out["step_layout"] = dict(params="packed-planes",
                              scatters_per_step=2, gathers_per_step=2,
                              unpacked_scatters_per_step=6)

    pp0 = model.pack_params(params0)
    for label, use_kernels in (("sched", False), ("kernel", True)):
        impl = resolve_impl("auto") if use_kernels else "ref"
        with reg.span(f"train.compile.{label}"):
            fn = sgd.train_epoch_scheduled.lower(
                pp0, sd, sched, keys(0), jnp.asarray(0), hp,
                use_kernels=use_kernels, impl=impl,
                interpret=jax.default_backend() == "cpu").compile()
        pp_end, times = run_epochs(
            fn, lambda ep: (sd, sched, keys(ep), jnp.asarray(ep), hp),
            copy(pp0), epochs, reg, f"train.epoch.{label}")
        sec = min(times)
        out[label] = dict(sec_per_epoch=sec, updates_per_sec=sp.nnz / sec,
                          compile_sec=reg.span_durations(
                              f"train.compile.{label}")[-1],
                          rmse=ev(model.unpack_params(pp_end)))
        emit(f"train.{label}.{name}", sec,
             f"ups={sp.nnz / sec:,.0f};speedup={out['base']['sec_per_epoch'] / sec:.2f}x")
        if label == "sched" and measure_overhead:
            # instrumentation-cost gate on the hot path: re-run the same
            # compiled fn with spans on vs off (ISSUE 6 target: ≤ 2%)
            out["obs_overhead"] = obs_overhead(
                fn, lambda ep: (sd, sched, keys(ep), jnp.asarray(ep), hp),
                pp0, epochs, copy)
            emit(f"train.obs_overhead.{name}",
                 out["obs_overhead"]["enabled_sec_per_epoch"],
                 f"frac={out['obs_overhead']['overhead_frac']:+.4f}")

    out["speedup_sched"] = out["base"]["sec_per_epoch"] / out["sched"]["sec_per_epoch"]
    out["speedup_kernel"] = out["base"]["sec_per_epoch"] / out["kernel"]["sec_per_epoch"]
    return out


def check(results) -> list[str]:
    """Regression gate against the BENCH_train.json floors."""
    fails = []
    for r in results:
        cf = r["schedule"]["cf_frac"]
        floor = CHECK_SPEEDUP_SMOKE if r["name"] == "smoke" else CHECK_SPEEDUP
        if cf < CHECK_CF_FRAC:
            fails.append(f"{r['name']}: cf_frac {cf:.3f} < {CHECK_CF_FRAC}")
        if r["speedup_sched"] < floor:
            fails.append(f"{r['name']}: speedup_sched "
                         f"{r['speedup_sched']:.2f} < {floor}")
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", default="small,medium,large")
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_train.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config + 2 epochs (CI gate; still writes --out)")
    ap.add_argument("--check", action="store_true",
                    help="assert speedup/cf_frac floors after the run "
                         "(exit 1 on regression)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the run's obs spans as Chrome trace-event "
                         "JSON (load in Perfetto / chrome://tracing)")
    args = ap.parse_args(argv)
    if args.trace:
        obs.enable()   # scoped() registries below collapse onto the
                       # shared one so the trace covers the whole run

    scales = ["smoke"] if args.smoke else [s for s in args.scales.split(",") if s]
    # --check under --smoke gates CI on a wall-clock floor: min-of-2 epochs
    # has almost no rejection against this box's noisy neighbours, so give
    # the gate 5 epochs (smoke epochs are ~10 ms; compiles dominate anyway)
    epochs = (5 if args.check else 2) if args.smoke else args.epochs
    results = []
    for name in scales:
        results.append(bench_scale(name, epochs=epochs, seed=args.seed))

    doc = dict(
        benchmark="bench_train",
        backend=jax.default_backend(),
        jax_version=jax.__version__,
        protocol=dict(epochs=epochs, timing="min sec/epoch over the run "
                      "(noise-robust on shared boxes), AOT-compiled "
                      "(compile excluded), donated params, tiered "
                      "conflict-free schedule; epochs timed as repro.obs "
                      "spans (single timing source), obs_overhead = "
                      "enabled/disabled median-epoch ratio - 1 over "
                      "interleaved order-swapped rounds (target ≤0.02)",
                      floors=dict(cf_frac=CHECK_CF_FRAC,
                                  speedup=CHECK_SPEEDUP,
                                  speedup_smoke=CHECK_SPEEDUP_SMOKE)),
        scales=results,
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    if args.trace:
        obs.write_trace(args.trace)
        print(f"# trace: {args.trace} "
              f"({len(obs.chrome_trace()['traceEvents'])} events)")

    for r in results:
        st = r["schedule"]
        print(f"# {r['name']}: M={r['M']} N={r['N']} nnz={r['nnz']} | "
              f"base {r['base']['sec_per_epoch']:.3f}s/ep | "
              f"sched {r['sched']['sec_per_epoch']:.3f}s/ep "
              f"({r['speedup_sched']:.2f}x, cf={st['cf_frac']:.2f}) | "
              f"kernel {r['kernel']['sec_per_epoch']:.3f}s/ep "
              f"({r['speedup_kernel']:.2f}x) | rmse "
              f"{r['base']['rmse']:.4f}/{r['sched']['rmse']:.4f}/"
              f"{r['kernel']['rmse']:.4f}")

    if args.check:
        fails = check(results)
        for f_ in fails:
            print(f"CHECK FAIL: {f_}", file=sys.stderr)
        if fails:
            sys.exit(1)
        floors = ",".join(
            str(CHECK_SPEEDUP_SMOKE if n == "smoke" else CHECK_SPEEDUP)
            for n in scales)
        print(f"# check passed: cf_frac ≥ {CHECK_CF_FRAC}, "
              f"speedup_sched ≥ {floors} on {','.join(scales)}")
    return results


if __name__ == "__main__":
    compile_cache.enable()
    main()
