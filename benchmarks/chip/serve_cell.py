"""Generator of the ``serve`` mixes: users asking a `RecsysService`
for their top-N at a fixed offered rate.

Set-up: the catalog is made on the device (`gen.catalog`: one catalog,
its item ids permuted by the seed), then the simLSH signatures and the
index under the catalog's own hash key (span ``bench.index``), then the
service, its warm-up, and a look at the compiled flush program for the
two kernels of the walk path.

Window: `gen.arrivals` gives each request its due time and user.  A
single thread submits every request that is due, as soon as it is due,
and collects the answers with `take_results`; it never flushes, since
batching belongs to the program.  After the last due time the service is
drained with `flush`, and late answers count with their whole wait.  Each
request's latency runs from its due time to the moment `take_results`
handed its answer back; ``serve_p90_ms`` is the 90th percentile over
every request of the window (a request never answered counts as
infinitely late).  The 99th, which one stall of the host in the window
sets, is the per-layer ``serve.p99_ms``.

Correctness, once the window has closed: every answer is checked for
shape (``topn`` distinct valid ids, scores in descending order) and its
scores against the plain reference's scores of the same (user, item)
pairs; a sample, drawn from the seed, of the distinct users served is
checked for recall (each by its first answer) against the reference's
exact top-N over the whole catalog.
"""
from __future__ import annotations

import gc
import re
import shutil
import time

import numpy as np

import gen
import harness
import xplane

KERNELS = ("lsh_retrieve_topc", "candidate_score_topn")


class GcClock:
    """Collections of Python's cyclic garbage collector inside a block:
    ``done`` = [(generation, seconds)]."""

    def __enter__(self):
        self.done, self._t = [], None
        gc.callbacks.append(self._tick)
        return self

    def _tick(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.done.append((info["generation"],
                              time.perf_counter() - self._t))

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._tick)

    def summary(self) -> str:
        g2 = [s for g, s in self.done if g == 2]
        return (f"{len(self.done)} collections, {len(g2)} of generation 2 "
                f"(worst {max(g2, default=0.0) * 1e3:.3f} ms), "
                f"{sum(s for _, s in self.done) * 1e3:.3f} ms in all")


def slow_tail(due: np.ndarray, lat: np.ndarray, gap_s: float = 0.1) -> str:
    """Where the slowest 1 % of requests were due: how many bursts they
    form (due times more than ``gap_s`` apart start a new one) and the
    largest burst's share and span."""
    slow = np.sort(due[lat >= np.percentile(lat, 99, method="higher")])
    cuts = np.flatnonzero(np.diff(slow) > gap_s) + 1
    parts = np.split(slow, cuts)
    big = max(parts, key=len)
    return (f"slowest 1 %: {len(parts)} bursts, the largest "
            f"{big.size / slow.size:.3f} of them, due {big[0]:.3f}-"
            f"{big[-1]:.3f} s")


def kernels_in(hlo: str) -> set:
    """Names of the Pallas kernels a compiled TPU program runs."""
    return {name for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for name in re.findall(r"jit\((\w+)\)/pallas_call", line)}


def build(run: harness.Run, reg):
    """(params, catalog arrays, service) — everything before the window."""
    import jax
    import jax.numpy as jnp
    from repro.core import simlsh
    from repro.core.model import Params
    from repro.data.sparse import from_coo
    from repro.serve import RecsysService, ServeConfig, build_index

    cfg = run.cfg
    U, V, bh, rows, cols, vals = gen.catalog(cfg["catalog"], run.seed)
    M, N = U.shape[0], V.shape[0]
    mu = jnp.asarray(cfg["catalog"]["mu"], jnp.float32)
    b = jnp.zeros((M,), jnp.float32)
    params = Params(U=U, V=V, b=b, bh=bh, W=jnp.zeros((N, 1)),
                    C=jnp.zeros((N, 1)), mu=mu)
    sp = from_coo(rows, cols, vals, (M, N))
    with reg.span("bench.index"):
        # the deployment's one index: its hash key comes with the catalog,
        # so every seed serves the same buckets under its own item ids
        sigs = simlsh.encode(sp, simlsh.SimLSHConfig(**cfg["lsh"]),
                             gen.key_of(cfg["catalog"]["catalog_seed"], 5))
        index = build_index(sigs, tail_cap=cfg["tail_cap"])
        jax.block_until_ready(index.sorted_ids)
    svc = RecsysService(params, index, sp, ServeConfig(**cfg["serve"]),
                        registry=reg)
    svc.warmup()
    return (U, V, bh, mu, b), svc


def window(svc, due, users, topn: int, trace=None):
    """Offer the schedule to ``svc``; drain it after the last due time.
    ``trace`` = (start_s, stop_s) marks that part of the window with the
    ``bench.window`` annotation, for a profiler the caller started.
    → (answer times, items, scores, generator lag, window close), times
    in seconds from the window start; NaN where no answer came."""
    import jax
    n = due.size
    got_at = np.full(n, np.nan)
    items = np.zeros((n, topn), np.int32)
    scores = np.zeros(items.shape, np.float32)
    lag = np.zeros(n)
    tracing = {}
    cursor = 0

    def collect(now: float) -> None:
        nonlocal cursor
        for _, s, it in svc.take_results():
            k = it.shape[0]
            got_at[cursor:cursor + k] = now
            items[cursor:cursor + k] = it
            scores[cursor:cursor + k] = s
            cursor += k

    svc.take_results()
    t_w0 = time.perf_counter()
    i = 0
    while i < n:
        now = time.perf_counter() - t_w0
        if trace and not tracing and now >= trace[0]:
            tracing["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tracing["ann"].__enter__()
        elif tracing.get("ann") and now >= trace[1]:
            tracing["ann"].__exit__(None, None, None)
            tracing["ann"] = None       # once: the key stays, so no re-entry
        if due[i] > now:
            wait = due[i] - now
            if wait > 2e-4:
                time.sleep(wait - 1e-4)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        lag[i:j] = now - due[i:j]
        svc.submit(users[i:j])
        i = j
        collect(time.perf_counter() - t_w0)
    t_close = time.perf_counter() - t_w0
    if tracing.get("ann"):
        tracing["ann"].__exit__(None, None, None)
    svc.flush()
    collect(time.perf_counter() - t_w0)
    return t_w0, got_at, items, scores, lag, t_close


def run(run: harness.Run, trace_dir: str, devices) -> None:
    import jax
    from repro import obs

    cfg, traffic = run.cfg, run.traffic
    reg = obs.Registry(enabled=True, jax_annotations=run.trace_on)
    arrays, svc = build(run, reg)
    if jax.default_backend() == "tpu":
        found = kernels_in(svc.flush_hlo())
        run.ok = set(KERNELS) <= found
        run.notes.append(f"flush kernels: {sorted(found)}")
    M = arrays[0].shape[0]
    due, users = gen.arrivals(traffic["rate_users_per_s"], run.seconds, M,
                              traffic["zipf_a"], run.seed)
    n = due.size
    tr = None
    if run.trace_on:
        # started before the window: starting a profiler stalls the
        # thread for seconds, which the generator must not absorb
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        tr = (traffic["trace_from_s"],
              traffic["trace_from_s"] + traffic["trace_s"])
    setup = time.perf_counter() - run.t_start
    run.end_to_end["setup_s"] = setup
    with GcClock() as gcs:
        t_w0, got_at, items, scores, lag, t_close = window(
            svc, due, users, cfg["serve"]["topn"], tr)
    if run.trace_on:
        jax.profiler.stop_trace()
    st = svc.stats()
    run.read_memory_peak(devices)

    lat = (got_at - due) * 1e3
    answered = np.isfinite(got_at)
    run.attempted = n
    bad = ~answered | ~well_formed(items, scores, arrays[1].shape[0])
    # an answer from the exact fallback or a degraded flush comes from
    # another path than the walk the cell measures: the run is not correct
    degraded = st["degraded"] + st["fallbacks"] * cfg["serve"]["micro_batch"]
    run.failed = int(bad.sum()) + int(degraded)
    run.ok = run.ok and not bad.any() and degraded == 0
    every = np.where(answered, lat, np.inf)
    # nearest rank: an interpolation between two infinite waits is NaN
    run.end_to_end["serve_p90_ms"] = float(
        np.percentile(every, 90, method="higher"))
    run.spans = [(nm, t, d) for nm, t, d, _, _ in reg.spans]
    flushes = [(t, d) for nm, t, d in run.spans if nm == "serve.flush"]
    run.facts.update(
        latency_ms=every, due_ns=(t_w0 + due) * 1e9, flushes=flushes,
        micro_batch=cfg["serve"]["micro_batch"], F=cfg["catalog"]["F"],
        span_names=("serve.flush", "serve.flush.dispatch", "bench.window"))
    run.notes.append(
        f"set-up {setup:.3f} s; "
        f"generator lag behind due time: median {np.median(lag) * 1e3:.3f} "
        f"ms, worst {lag.max() * 1e3:.3f} ms; {n} requests due over "
        f"{run.seconds} s, window closed at {t_close:.3f} s; latency ms "
        + ", ".join(f"p{q} {np.percentile(every, q, method='higher'):.3f}"
                    for q in (50, 90, 95, 99, 99.9))
        + "; flushes "
        f"{st['batches']}, degraded {st['degraded']}, fallbacks "
        f"{st['fallbacks']}")
    run.notes.append(
        f"in the window: garbage collector {gcs.summary()}; longest flush "
        f"{max((d for _, d in flushes), default=0) * 1e-6:.3f} ms; "
        + slow_tail(due, every))
    del svc
    compare(run, arrays, users, items, scores, answered)
    if run.trace_on:
        run.trace = xplane.load(trace_dir, window="bench.window")


def well_formed(items, scores, N: int) -> np.ndarray:
    """Per answer: ids in range, distinct, scores descending."""
    ok = (items >= 0).all(1) & (items < N).all(1)
    srt = np.sort(items, 1)
    ok &= (srt[:, 1:] != srt[:, :-1]).all(1)
    ok &= (np.diff(scores, axis=1) <= 0).all(1)
    return ok


def compare(run, arrays, users, items, scores, answered) -> None:
    import jax.numpy as jnp
    cfg, traffic = run.cfg, run.traffic
    ref = harness.reference(cfg)
    U, V, bh, mu, b = arrays
    lim = cfg["limits"]
    t0 = time.perf_counter()
    idx = np.flatnonzero(answered)
    gap = 0.0
    for s in range(0, idx.size, 65536):
        k = idx[s:s + 65536]
        want = np.asarray(ref.pair_scores(U, V, bh, mu, b,
                                          jnp.asarray(users[k]),
                                          jnp.asarray(items[k])))
        gap = max(gap, float(np.max(np.abs(scores[k] - want))))
    # recall over distinct users, each by its first answer: drawn over
    # requests, a few Zipf-head users would weigh a tenth of the sample
    rng = np.random.default_rng(int(run.seed) + 1)
    _, first = np.unique(users[idx], return_index=True)
    pick = idx[rng.choice(first, size=min(traffic["recall_sample"],
                                          first.size), replace=False)]
    topn = cfg["serve"]["topn"]
    exact = ref.exact_topn(U, V, bh, mu, b, users[pick], topn=topn)
    hits = sum(len(set(items[p]) & set(exact[q]))
               for q, p in enumerate(pick))
    recall = hits / exact.size
    run.end_to_end["recall_at_10"] = recall
    run.check("score_gap", gap, lim["score_gap"])
    run.check("miss_share", 1.0 - recall, lim["miss_share"])
    run.notes.append(f"reference: {time.perf_counter() - t0:.3f} s over "
                     f"{idx.size} answers and {pick.size} sampled users of "
                     f"{first.size} served")
