"""repro.obs: histogram quantiles, span nesting, disabled-mode no-ops,
exporters, and the RecsysService.stats() single-source-of-truth parity
(ISSUE 6)."""
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import simlsh
from repro.core.model import init_from_data
from repro.core.simlsh import SimLSHConfig
from repro.data.sparse import from_coo
from repro.obs.registry import (_B_PER_DECADE, _NULL_SPAN, Histogram,
                                Registry)
from repro.serve import RecsysService, ServeConfig, build_index


# ---------------------------------------------------------------- histogram

def test_histogram_quantiles_match_numpy_within_bucket_error():
    """p50/p95/p99 from the fixed log-bucket histogram vs exact numpy
    percentiles on lognormal samples (latency-shaped).  The bucket grid
    is 16/decade → ratio 10^(1/16) between bounds, so the log-linear
    interpolation is off by at most that ratio (~15.5%); in practice it
    lands ~1% out."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-6.0, sigma=1.0, size=20_000)   # ~ms spans
    h = Histogram()
    for x in xs:
        h.observe(float(x))
    bound = 10.0 ** (1.0 / _B_PER_DECADE) - 1.0
    for q in (0.50, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        got = h.quantile(q)
        assert abs(got - exact) / exact < bound, (q, got, exact)
    assert h.count == xs.size
    np.testing.assert_allclose(h.sum, xs.sum(), rtol=1e-9)
    assert h.min == xs.min() and h.max == xs.max()


def test_histogram_exact_stats_and_edge_cases():
    h = Histogram()
    assert h.summary() == dict(count=0)
    assert np.isnan(h.quantile(0.5))
    for v in (0.0, 1e-12, 1e9):          # under/over the bucket range
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 0.0 and s["max"] == 1e9
    # quantiles stay clamped to observed extremes, never a bucket bound
    assert 0.0 <= h.quantile(0.01) <= 1e9
    assert h.quantile(0.999) == 1e9


def test_histogram_single_value_all_quantiles_equal():
    h = Histogram()
    h.observe(0.25)
    for q in (0.0, 0.5, 0.99):
        assert h.quantile(q) == pytest.approx(0.25, rel=1e-12)


# ---------------------------------------------------------------- spans

def test_span_nesting_depth_and_chrome_trace_containment():
    reg = Registry(enabled=True)
    with reg.span("outer"):
        time.sleep(0.002)
        with reg.span("inner.a"):
            time.sleep(0.002)
        with reg.span("inner.b"):
            time.sleep(0.002)
    # completion order: children first; depths from the thread stack
    names = [s[0] for s in reg.spans]
    depths = {s[0]: s[4] for s in reg.spans}
    assert names == ["inner.a", "inner.b", "outer"]
    assert depths == {"outer": 0, "inner.a": 1, "inner.b": 1}

    doc = obs.chrome_trace(reg)
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert doc["displayTimeUnit"] == "ms"
    out, a, b = evs["outer"], evs["inner.a"], evs["inner.b"]
    # Perfetto reconstructs nesting from interval containment per tid:
    # both children inside the parent, siblings disjoint and ordered
    assert out["tid"] == a["tid"] == b["tid"]
    assert out["ts"] <= a["ts"] and a["ts"] + a["dur"] <= out["ts"] + out["dur"]
    assert out["ts"] <= b["ts"] and b["ts"] + b["dur"] <= out["ts"] + out["dur"]
    assert a["ts"] + a["dur"] <= b["ts"]
    json.dumps(doc)     # must be valid JSON end to end


def test_span_durations_and_histogram_feed():
    reg = Registry(enabled=True)
    for _ in range(3):
        with reg.span("work"):
            time.sleep(0.001)
    durs = reg.span_durations("work")
    assert len(durs) == 3 and all(d >= 0.001 for d in durs)
    # every span completion also lands in the same-named histogram
    assert reg.hist_summary("work")["count"] == 3


def test_record_span_for_overlapping_intervals():
    """Externally-timed (dispatch-ahead) intervals may overlap — the
    registry must keep both verbatim."""
    reg = Registry(enabled=True)
    t0 = time.perf_counter_ns()
    reg.record_span("flush", t0, 5_000_000)
    reg.record_span("flush", t0 + 1_000_000, 5_000_000)   # overlaps the 1st
    assert len(reg.span_durations("flush")) == 2
    assert reg.hist_summary("flush")["count"] == 2


def test_span_log_cap_drops_but_histogram_never_does():
    reg = Registry(enabled=True, max_spans=4)
    for i in range(10):
        reg.record_span("s", i * 100, 50)
    assert len(reg.spans) == 4 and reg.spans_dropped == 6
    assert reg.hist_summary("s")["count"] == 10


def test_spans_thread_local_stacks():
    reg = Registry(enabled=True)

    def worker():
        with reg.span("t.outer"):
            with reg.span("t.inner"):
                pass

    with reg.span("main.outer"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    d = {s[0]: s[4] for s in reg.spans}
    # each thread nests against its own stack, not a shared one
    assert d == {"t.inner": 1, "t.outer": 0, "main.outer": 0}
    tids = {s[0]: s[3] for s in reg.spans}
    assert tids["t.outer"] != tids["main.outer"]


# ---------------------------------------------------------------- disabled

def test_disabled_mode_is_noop_and_allocation_free():
    reg = Registry(enabled=False)
    # warm up any lazy state (method binding caches etc.)
    for _ in range(3):
        with reg.span("x"):
            pass
        reg.counter_add("c")
        reg.gauge_set("g", 1.0)
        reg.observe("h", 0.5)
        reg.event("e", k=1)
    assert reg.span("x") is _NULL_SPAN          # shared singleton, no alloc
    before = sys.getallocatedblocks()
    for _ in range(5_000):
        with reg.span("x"):
            pass
        reg.counter_add("c")
        reg.gauge_set("g", 1.0)
        reg.observe("h", 0.5)
    after = sys.getallocatedblocks()
    # zero net allocation across 20k recording calls (tolerance for
    # interpreter-internal churn unrelated to the registry)
    assert after - before < 16, (before, after)
    assert not reg.counters and not reg.gauges and not reg.hists
    assert not reg.spans and not reg.events
    s = reg.snapshot()
    assert s["counters"] == {} and s["histograms"] == {}


def test_module_default_disabled_and_scoped():
    assert not obs.enabled()    # library default: opted out
    r = obs.scoped()
    assert r is not obs.get() and r.enabled
    try:
        obs.enable()
        assert obs.scoped() is obs.get()
    finally:
        obs.disable()
        obs.reset()


# ---------------------------------------------------------------- exporters

def test_events_jsonl_roundtrip():
    reg = Registry(enabled=True)
    reg.event("eval", epoch=1, rmse=0.91)
    reg.event("eval", epoch=2, rmse=0.88)
    lines = obs.events_jsonl(reg).strip().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert [r["event"] for r in recs] == ["eval", "eval"]
    assert recs[1]["rmse"] == 0.88 and "ts" in recs[0]


def test_prometheus_text_exposition():
    reg = Registry(enabled=True)
    reg.counter_add("serve.users", 42)
    reg.gauge_set("serve.queue_depth", 3)
    reg.observe("serve.flush", 0.01)
    txt = obs.prometheus_text(reg)
    assert "# TYPE serve_users counter\nserve_users 42" in txt
    assert "# TYPE serve_queue_depth gauge\nserve_queue_depth 3" in txt
    assert '# TYPE serve_flush summary' in txt
    assert 'serve_flush{quantile="0.50"}' in txt
    assert "serve_flush_count 1" in txt


# ------------------------------------------------- service stats() parity

@pytest.fixture(scope="module")
def tiny_service():
    rng = np.random.default_rng(0)
    M, N = 200, 60
    rows = np.repeat(np.arange(M), 5).astype(np.int32)
    cols = rng.integers(0, N, M * 5).astype(np.int32)
    vals = rng.integers(1, 6, M * 5).astype(np.float32)
    keys = rows.astype(np.int64) * N + cols
    _, uniq = np.unique(keys, return_index=True)
    sp = from_coo(rows[uniq], cols[uniq], vals[uniq], (M, N))
    cfg = SimLSHConfig(G=8, p=2, q=8)
    sigs = simlsh.encode(sp, cfg, jax.random.PRNGKey(0))
    index = build_index(sigs, tail_cap=32)
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    scfg = ServeConfig(topn=5, micro_batch=16, C=48, n_seeds=4, cap=8,
                       n_popular=8)
    return params, index, sp, scfg, sigs, cfg


def test_service_stats_parity_with_registry(tiny_service):
    """stats() is a pure read of the obs registry: same counters, same
    span histogram, pre-obs key semantics preserved."""
    params, index, sp, scfg, _, _ = tiny_service
    svc = RecsysService(params, index, sp, scfg).warmup()
    for _ in range(3):
        svc.submit(np.arange(16, dtype=np.int32))
    svc.flush()
    st = svc.stats()
    reg = svc.obs
    assert st["mode"] == "candidate"
    assert st["batches"] == int(reg.counter("serve.flushes")) == 3
    assert st["users"] == int(reg.counter("serve.users")) == 48
    busy = reg.counter("serve.busy_seconds")
    assert st["qps"] == pytest.approx(st["users"] / busy)
    secs = np.asarray(reg.span_durations("serve.flush"))
    assert secs.shape[0] == 3
    for key, q in (("p50_ms", 50), ("p95_ms", 95), ("p99_ms", 99)):
        assert st[key] == pytest.approx(float(np.percentile(secs, q) * 1e3))
    assert st["queue"] == 0
    assert st["ingest_to_servable_s"] == 0.0    # no ingest yet
    # fill wait: one span per real flush, none per request
    assert len(reg.span_durations("serve.flush.fill")) == 3
    assert reg.hist_summary("serve.queue_wait")["count"] == 0


FLUSH_SPANS = ("serve.flush.fill", "serve.flush.dispatch",
               "serve.flush.dispatch.take", "serve.flush.dispatch.launch",
               "serve.flush.sync", "serve.flush")


def test_service_spans_once_per_real_flush(tiny_service):
    """Each real flush records fill, dispatch (holding take and launch),
    sync and the flush span once; the degraded pseudo-flush of shed
    requests, which never reaches the device, records none.  A flush's
    fill starts at the submit of its oldest request."""
    import dataclasses
    params, index, sp, scfg, _, _ = tiny_service
    reg = Registry(enabled=True)
    svc = RecsysService(params, index, sp,
                        dataclasses.replace(scfg, max_pending=24),
                        registry=reg).warmup()
    svc.submit(np.arange(10, dtype=np.int32))
    t_oldest = svc._pending[0][1]
    svc.submit(np.arange(10, 20, dtype=np.int32))     # flush 1 (16 users)
    svc.submit(np.arange(40, dtype=np.int32))    # shed 20, flush 2 (16)
    svc.flush()                                  # flush 3 (8, padded)
    assert reg.counter("serve.flushes") == 3
    assert reg.counter("serve.degraded_users") == 20
    assert len(svc.take_results()) == 4          # 3 real + 1 degraded
    by = {n: [sp_ for sp_ in reg.spans if sp_[0] == n] for n in FLUSH_SPANS}
    for name in FLUSH_SPANS:
        assert len(by[name]) == 3, name
    assert by["serve.flush.fill"][0][1] == t_oldest
    for k, (_, d0, dd, _, depth) in enumerate(by["serve.flush.dispatch"]):
        fill = by["serve.flush.fill"][k]
        assert fill[1] + fill[2] <= d0          # fill ends as dispatch starts
        for child in ("serve.flush.dispatch.take",
                      "serve.flush.dispatch.launch"):
            _, c0, cd, _, cdepth = by[child][k]
            assert d0 <= c0 and c0 + cd <= d0 + dd and cdepth == depth + 1
        # the flush span starts inside launch; the sync lies after dispatch
        assert d0 <= by["serve.flush"][k][1] <= d0 + dd
        assert by["serve.flush.sync"][k][1] >= d0 + dd
    assert reg.hist_summary("serve.queue_wait")["count"] == 0


def test_trace_clock_recovers_offset_and_rate():
    """Twins (spans on both the registry's and a profile's clock) fix
    the map between them, though the profile covers only the middle of
    the registry's spans; spans without a twin are left out."""
    rng = np.random.default_rng(5)
    rate, offset = 1.0 + 3.7e-6, -83_712_345_678_901.25
    to_trace = lambda t: offset + rate * t
    spans, t = [], 91_234_567_890_123            # perf_counter_ns-like
    for k in range(400):
        name = ("serve.flush.dispatch", "serve.flush.sync")[k % 2]
        dur = int(rng.integers(50_000, 5_000_000))
        spans.append((name, t, dur, 1, 0))
        spans.append(("serve.flush", t, dur + 7, 1, 0))    # registry only
        t += dur + int(rng.integers(10_000, 20_000_000))
    mid = spans[300:560]
    host = [(n, to_trace(a), to_trace(a + d)) for n, a, d, _, _ in mid
            if n != "serve.flush"]
    host.append(("bench.window", to_trace(mid[0][1]), to_trace(t)))
    clk = obs.trace_clock(spans, host)
    assert clk.twins == len(host) - 1
    assert clk.residual_us < 1.0
    assert abs(clk.rate - rate) < 1e-10
    t_mid = mid[100][1]
    assert abs(clk(t_mid) - to_trace(t_mid)) < 1.0            # ns
    assert abs(clk.offset_ns - offset) < 1e3    # 1 µs, 25 h back at t = 0
    placed = clk.place(mid, "serve.flush")
    assert len(placed) == 130
    assert abs(placed[0][0] - to_trace(mid[1][1])) < 1.0
    assert obs.trace_clock(spans, [("bench.window", 0.0, 1.0)]) is None


def test_sibling_services_isolated_but_spans_mirror(tiny_service):
    """Two services must never blend each other's stats() (the shared-
    registry regression: a full-mode service's users/busy deflated a
    candidate service's reported QPS under --trace), while both still
    contribute their flush spans to an enabled process-wide registry via
    the span mirror."""
    params, index, sp, scfg, _, _ = tiny_service
    shared = Registry(enabled=True)
    a = RecsysService(params, index, sp, scfg,
                      registry=Registry(enabled=True, mirror=shared))
    b = RecsysService(params, index, sp, scfg,
                      registry=Registry(enabled=True, mirror=shared))
    a.warmup()
    b.warmup()
    for _ in range(2):
        a.submit(np.arange(16, dtype=np.int32))
    a.flush()
    b.submit(np.arange(16, dtype=np.int32))
    b.flush()
    sa, sb = a.stats(), b.stats()
    # isolation: each service reports only its own traffic
    assert sa["batches"] == 2 and sa["users"] == 32
    assert sb["batches"] == 1 and sb["users"] == 16
    assert sa["qps"] == pytest.approx(
        32 / a.obs.counter("serve.busy_seconds"))
    # mirror: the shared timeline carries every flush span from both,
    # but none of their metric planes
    assert len(shared.span_durations("serve.flush")) == 3
    assert shared.counter("serve.users") == 0.0
    assert shared.hist_summary("serve.flush")["count"] == 0
    # a disabled mirror target records nothing
    off = Registry(enabled=False)
    c = RecsysService(params, index, sp, scfg,
                      registry=Registry(enabled=True, mirror=off))
    c.warmup()
    c.submit(np.arange(16, dtype=np.int32))
    c.flush()
    assert c.stats()["batches"] == 1
    assert off.spans == []


def test_service_empty_stats():
    """Zero-traffic stats must not divide by zero or produce NaN."""
    rng = np.random.default_rng(3)
    M, N = 64, 32
    rows = np.repeat(np.arange(M), 3).astype(np.int32)
    cols = rng.integers(0, N, M * 3).astype(np.int32)
    vals = np.ones(M * 3, np.float32)
    keys = rows.astype(np.int64) * N + cols
    _, uniq = np.unique(keys, return_index=True)
    sp = from_coo(rows[uniq], cols[uniq], vals[uniq], (M, N))
    sigs = simlsh.encode(sp, SimLSHConfig(G=8, p=2, q=4),
                         jax.random.PRNGKey(0))
    svc = RecsysService(init_from_data(jax.random.PRNGKey(1), sp, 8, 4),
                        build_index(sigs, tail_cap=8), sp,
                        ServeConfig(micro_batch=8, C=16, n_seeds=2,
                                    n_popular=0))
    st = svc.stats()
    assert st["batches"] == 0 and st["users"] == 0 and st["qps"] == 0.0
    assert st["p50_ms"] == 0.0 and st["p95_ms"] == 0.0


def test_service_ingest_sets_servable_latency_and_trace(tiny_service):
    """The acceptance path: ingest → stats()['ingest_to_servable_s'] > 0,
    and a profiled flush exports nested retrieve/walk/score spans that
    a Chrome-trace consumer can reconstruct (walk-path layout: dedup is
    in-kernel/at-select, so there is no dedup span)."""
    params, index, sp, scfg, sigs, lshcfg = tiny_service
    svc = RecsysService(params, index, sp, scfg).warmup()
    svc.profile_flush()
    sig2 = simlsh.encode(sp, lshcfg, jax.random.PRNGKey(7))
    svc.ingest(sig2[:, :4], jnp.arange(sp.N, sp.N + 4, dtype=jnp.int32))
    st = svc.stats()
    assert st["ingest_to_servable_s"] > 0.0

    doc = obs.chrome_trace(svc.obs)
    evs = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            evs.setdefault(e["name"], e)
    for name in ("serve.flush", "serve.flush.retrieve",
                 "serve.flush.retrieve.desc", "serve.flush.retrieve.walk",
                 "serve.flush.score", "serve.flush.select", "serve.ingest"):
        assert name in evs, name
    fl, rt, wk = (evs["serve.flush"], evs["serve.flush.retrieve"],
                  evs["serve.flush.retrieve.walk"])
    inside = lambda a, b: (b["ts"] <= a["ts"]
                           and a["ts"] + a["dur"] <= b["ts"] + b["dur"])
    assert inside(rt, fl) and inside(wk, rt)
    assert inside(evs["serve.flush.score"], fl)
    assert inside(evs["serve.flush.select"], fl)


def test_service_profile_flush_matches_fused_results(tiny_service):
    """The staged profiling path must run the same retrieval+scoring as
    the fused hot path (same candidates in, same top-N out)."""
    params, index, sp, scfg, _, _ = tiny_service
    svc = RecsysService(params, index, sp, scfg).warmup()
    users = np.arange(16, dtype=np.int32)
    svc.submit(users)
    svc.flush()
    _, fused_scores, fused_items = svc.take_results()[0]
    svc.profile_flush(users)   # records spans; results discarded
    # re-run the staged path manually for output parity
    from repro.kernels.candidate_score.ops import score_candidates
    from repro.serve.retrieve import candidate_pool, finalize_candidates
    ids = jnp.asarray(users)
    pool = candidate_pool(index, sp, ids, n_seeds=scfg.n_seeds,
                          cap=scfg.cap, JK=svc.JK, window=scfg.seed_window,
                          fold_mates=scfg.fold_mates,
                          tail_scan=svc.index.tail_fill > 0)
    cand = finalize_candidates(pool, C=scfg.C, popular=svc.popular,
                               pool_width=scfg.resolved_pool_width())
    s, it = score_candidates(svc.planes, ids, cand, topn=scfg.topn,
                             tile_b=scfg.tile_b,
                             interpret=scfg.interpret_mode(),
                             impl=scfg.scorer_impl())
    np.testing.assert_array_equal(np.asarray(it), fused_items)
    np.testing.assert_allclose(np.asarray(s), fused_scores,
                               rtol=1e-5, atol=1e-5)
