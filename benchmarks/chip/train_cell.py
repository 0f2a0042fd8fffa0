"""Generator of the ``train`` mixes: an offline training job through
`repro.train.trainer.fit`, the entry users call.

Set-up: the ratings are made on the device from the seed (`gen.ratings`)
and so are the initial parameters, which reach `fit` through its own
checkpoint restore, so the reference starts from the same state.  `fit`
then searches neighbours, builds its schedule and planes, compiles (or
loads from the persistent cache) and runs the warm-up epochs.

Window: the ``n`` whole epochs after the warm-up, each with `fit`'s own
held-out evaluation, where ``n = max(min_window_epochs, ceil(seconds /
nominal_epoch_s))`` — a fixed amount of work for a given ``--seconds``,
about that long at the configuration's measured epoch time.  Its bounds
are read in `fit`'s ``log`` hook, which `fit` calls after each epoch's
evaluation.  ``train_ratings_per_s`` is the window's training ratings
over the window's time; ``heldout_rmse`` is `fit`'s RMSE after epoch
``heldout_epoch``.

Correctness, once the window has closed and the program's state is gone:
the plain reference (``configs/<config>.ref.py``) trains in float32 from
the same initial parameters through the first ``reference.epochs`` of
`fit`'s epochs, and three numbers are compared with the configuration's
limits — ``rmse_gap``, the relative gap of `fit`'s held-out RMSE after
the last of those epochs from the reference's; ``late_gain_gap``, how
much less `fit`'s RMSE fell than the reference's over the last quarter of
them; and ``eval_gap``, `fit`'s last reported RMSE against the
reference's evaluation of the model `fit` returned (its parameters and
neighbour lists).  The reference's ``dense`` returns whatever structure of
the training ratings its ``rmse`` reads: for ``ml10m-culsh`` the dense
[M, N] matrix, which a configuration with a large catalog cannot have (a
15,641 × 624,961 share would take 39 GB in float32), so such a
configuration's reference keeps its ratings sparse.  The later epochs
are the ones that tell: the Eq. (7) decay shrinks their steps below what
a lower precision resolves, while the program's early lag behind the
reference (its leftover batches average their collisions) closes there.
"""
from __future__ import annotations

import math
import shutil
import tempfile
import time

import numpy as np

import gen
import harness
import xplane

LEAVES = ("U", "V", "b", "bh", "W", "C")


def initial_params(d: dict, F: int, K: int, train, seed: int) -> dict:
    """θ₀ from the seed: U, V ~ N(0, 1/F), the rating baselines μ, b, b̂
    of the training ratings, W = C = 0."""
    import jax
    import jax.numpy as jnp
    M, N = d["M"], d["N"]

    @jax.jit
    def make(key, rows, cols, vals):
        ku, kv = jax.random.split(key)
        mu = jnp.mean(vals)
        cnt = lambda ids, n: jnp.zeros((n,)).at[ids].add(1.0)
        tot = lambda ids, n: jnp.zeros((n,)).at[ids].add(vals)
        dr, dc = cnt(rows, M), cnt(cols, N)
        b = jnp.where(dr > 0, tot(rows, M) / jnp.maximum(dr, 1) - mu, 0.0)
        bh = jnp.where(dc > 0, tot(cols, N) / jnp.maximum(dc, 1) - mu, 0.0)
        s = 1.0 / math.sqrt(F)
        return dict(U=jax.random.normal(ku, (M, F)) * s,
                    V=jax.random.normal(kv, (N, F)) * s, b=b, bh=bh,
                    W=jnp.zeros((N, K)), C=jnp.zeros((N, K)), mu=mu)

    return make(gen.key_of(seed, 3), *train)


def window_epochs(traffic: dict, seconds: float) -> int:
    return max(traffic["min_window_epochs"],
               math.ceil(seconds / traffic["nominal_epoch_s"]))


def run(run: harness.Run, trace_dir: str, devices) -> None:
    import jax
    from repro import obs
    from repro.core.model import Params
    from repro.train import checkpoint as ckpt
    from repro.train.trainer import FitConfig, fit

    cfg, traffic = run.cfg, run.traffic
    d, fc = cfg["data"], cfg["fit"]
    M, N, F, K = d["M"], d["N"], fc["F"], fc["K"]
    train, test, _ = gen.ratings(d, run.seed)
    p0 = initial_params(d, F, K, train, run.seed)
    ckdir = tempfile.mkdtemp(prefix="chipbench-init-")
    ckpt.save(ckdir, Params(**p0), step=0, sync=True)

    warm = traffic["warmup_epochs"]
    n_win = window_epochs(traffic, run.seconds)
    epochs = warm + n_win
    reg = obs.Registry(enabled=True, jax_annotations=run.trace_on)
    marks = []
    tracing = {}
    t_first = warm + traffic["trace_after_epochs"]

    def hook(msg: str) -> None:
        if not msg.startswith("epoch"):
            return
        marks.append(time.perf_counter())
        done = len(marks)
        if not run.trace_on:
            return
        if done == t_first:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tracing["ann"].__enter__()
        elif "ann" in tracing and done == t_first + traffic["trace_epochs"]:
            tracing.pop("ann").__exit__(None, None, None)
            jax.profiler.stop_trace()

    fit_cfg = FitConfig(F=F, K=K, method=fc["method"],
                        use_kernels=fc["use_kernels"],
                        kernel_impl=fc["kernel_impl"], epochs=epochs,
                        seed=run.seed & 0x7FFFFFFF, ckpt_dir=ckdir)
    t_fit = time.perf_counter()
    res = fit(train, test, (M, N), fit_cfg, log=hook, registry=reg)
    t_end = time.perf_counter()
    shutil.rmtree(ckdir, ignore_errors=True)
    if "ann" in tracing:
        tracing.pop("ann").__exit__(None, None, None)
        jax.profiler.stop_trace()

    n_train = int(train[0].shape[0])
    hist = [r for _, _, r in res.history]
    t0, t1 = marks[warm - 1], marks[-1]
    run.end_to_end["setup_s"] = t0 - run.t_start
    run.end_to_end["train_ratings_per_s"] = n_win * n_train / (t1 - t0)
    run.end_to_end["heldout_rmse"] = hist[traffic["heldout_epoch"] - 1]
    run.attempted = epochs
    run.failed = sum(1 for r in hist if not np.isfinite(r))
    run.ok = len(hist) == epochs and run.failed == 0
    run.spans = [(n, t, dd) for n, t, dd, _, _ in reg.spans]
    run.facts.update(
        n_train=n_train,
        span_names=("train.epoch", "train.epoch.eval", "bench.window"))
    spans: dict = {}
    for name, _, dd in run.spans:
        spans[name] = spans.get(name, 0.0) + dd * 1e-9
    run.notes.append(
        f"train window: {n_win} epochs of {n_train} ratings in "
        f"{t1 - t0:.3f} s; set-up {t0 - run.t_start:.3f} s (data and "
        f"initial state {t_fit - run.t_start:.3f} s); after the window "
        f"{t_end - t1:.3f} s; rmse {hist}; fit spans (s) "
        + ", ".join(f"{k} {v:.3f}" for k, v in spans.items()))
    run.read_memory_peak(devices)

    host = lambda a: np.asarray(a)
    p_prog = {k: host(getattr(res.params, k)) for k in LEAVES + ("mu",)}
    jk_prog = host(res.JK)
    del res
    compare(run, p0, train, test, hist, p_prog, jk_prog)
    if run.trace_on:
        run.trace = xplane.load(trace_dir, window="bench.window")


def compare(run, p0, train, test, hist, p_prog, jk_prog) -> None:
    """The reference trains from ``p0`` through the first epochs of
    `fit`'s and evaluates the model `fit` returned; the gaps go into
    ``run.checks``."""
    import jax.numpy as jnp
    cfg = run.cfg
    ref = harness.reference(cfg)
    d, fc, lim = cfg["data"], cfg["fit"], cfg["limits"]
    M, N = d["M"], d["N"]
    n = min(len(hist), cfg["reference"]["epochs"])
    t0 = time.perf_counter()
    _, curve, _ = ref.train(p0, train, test, M, N, fc["K"], n,
                            cfg["hyper"], gen.key_of(run.seed, 4),
                            batch=cfg["reference"]["batch"])
    R = ref.dense(*train, M, N)
    model = {k: jnp.asarray(v) for k, v in p_prog.items()}
    rmse_model = float(ref.rmse(model, R, jnp.asarray(jk_prog), *test))
    run.check("rmse_gap", abs(hist[n - 1] - curve[-1]) / curve[-1],
              lim["rmse_gap"])
    run.check("late_gain_gap", late_gain_gap(hist[:n], curve),
              lim["late_gain_gap"])
    run.check("eval_gap", abs(hist[-1] - rmse_model) / rmse_model,
              lim["eval_gap"])
    run.notes.append(
        f"reference: {time.perf_counter() - t0:.3f} s; rmse {curve}; "
        f"model rmse by the reference {rmse_model!r}")


def late_gain_gap(prog, ref) -> float:
    """How much less the program's held-out RMSE fell over the last
    quarter of the epochs (at least the last one) than the reference's
    did, as a share of the reference's fall (negative where the program
    fell more).  The latest epochs tell most: their decayed steps are the
    ones a lower precision cannot resolve."""
    h = len(ref) - max(1, len(ref) // 4)
    gain = lambda c: c[h - 1] - c[-1]
    return (gain(ref) - gain(prog)) / gain(ref)
