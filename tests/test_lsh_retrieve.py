"""`lsh_retrieve` kernel vs jnp oracle + walk-path building blocks.

Interpret-mode parity sweeps across cap/C/seed-count × empty/nonempty
tail × exclusion sets, plus property tests that the emitted candidates
are unique, come only from the probed bucket windows (∪ tail extras),
and never contain excluded ids.  The walk path that feeds the kernel —
`window_descriptors` (bitonic interval merge), `enumerate_windows`
(budgeted scatter-fill expansion), `tail_hits` (static prefix scan) and
`_select_topn_masked` (duplicate-masked top-n) — each get a brute-force
numpy oracle, and `recommend_walked` is checked end to end against
dedup-then-exact-score.  The candidate-routing heuristic rides along.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simlsh, topk
from repro.core.model import init_from_data, pack_serve_planes
from repro.core.simlsh import SimLSHConfig
from repro.data.sparse import from_coo
from repro.kernels.candidate_score.kernel import NEG
from repro.kernels.lsh_retrieve.kernel import lsh_retrieve_topc
from repro.kernels.lsh_retrieve.ops import retrieve_candidates
from repro.kernels.lsh_retrieve.ref import lsh_retrieve_topc_ref
from repro.serve import (RecsysService, ServeConfig, build_index,
                         enumerate_windows, full_topn, insert,
                         padded_flat_ids, recommend_walked, seed_items,
                         tail_hits, walk_candidates, window_descriptors,
                         window_slices)
from repro.serve.service import _select_topn_masked

SENTINEL = topk.SENTINEL


def _sparse(M=200, N=60, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(M), 6).astype(np.int32)
    cols = rng.integers(0, N, M * 6).astype(np.int32)
    vals = rng.integers(1, 6, M * 6).astype(np.float32)
    keys = rows.astype(np.int64) * N + cols
    _, uniq = np.unique(keys, return_index=True)
    return from_coo(rows[uniq], cols[uniq], vals[uniq], (M, N))


@pytest.fixture(scope="module")
def indexed():
    sp = _sparse()
    cfg = SimLSHConfig(G=8, p=2, q=8)
    sigs = simlsh.encode(sp, cfg, jax.random.PRNGKey(0))
    return sp, cfg, sigs, build_index(sigs, tail_cap=32)


@pytest.fixture(scope="module")
def indexed_tail(indexed):
    """Same catalog with five cloned items resident in the insert tail."""
    sp, cfg, sigs, index = indexed
    src = np.asarray([0, 3, 7, 11, 19])
    idx2 = insert(index, sigs[:, src],
                  jnp.asarray(sp.N + np.arange(5), jnp.int32))
    return sp, cfg, sigs, idx2


def _kernel_inputs(sp, index, *, B, n_seeds, cap, tail):
    users = jnp.arange(B, dtype=jnp.int32)
    seeds = seed_items(sp, users, n_seeds=n_seeds, window=32)
    starts, lens = window_slices(index, seeds, cap=cap)
    extra = (tail_hits(index, seeds) if tail
             else jnp.full((B, 1), SENTINEL, jnp.int32))
    return starts, lens, extra, padded_flat_ids(index, cap=cap)


def _pool_sets(starts, lens, extra, ids_flat):
    """Brute-force per-user candidate universe: every id inside the valid
    window prefixes, union the valid extras."""
    st, ln = np.asarray(starts), np.asarray(lens)
    ex, flat = np.asarray(extra), np.asarray(ids_flat)
    out = []
    for u in range(st.shape[0]):
        s = set()
        for i in range(st.shape[1]):
            s |= set(flat[st[u, i]:st[u, i] + ln[u, i]].tolist())
        s |= {int(x) for x in ex[u] if x != SENTINEL and x >= 0}
        out.append(s - {int(SENTINEL)})
    return out


# ------------------------------------------------------- kernel parity

@pytest.mark.parametrize("n_seeds,cap,C", [
    (4, 8, 32), (4, 8, 16), (8, 4, 64), (2, 16, 24), (5, 8, 48)])
@pytest.mark.parametrize("tail", [False, True])
@pytest.mark.parametrize("excl", [(), (1, 9), (SENTINEL,)])
def test_kernel_matches_ref_sweep(indexed, indexed_tail, n_seeds, cap, C,
                                  tail, excl):
    """Interpret-mode kernel ≡ jnp oracle, bit for bit, across descriptor
    geometries, tail occupancy, and exclusion sets (incl. the inert
    SENTINEL-only one the wrapper passes when there is no shortlist)."""
    sp, cfg, sigs, index = indexed_tail if tail else indexed
    starts, lens, extra, ids_flat = _kernel_inputs(
        sp, index, B=12, n_seeds=n_seeds, cap=cap, tail=tail)
    exclude = jnp.asarray(list(excl) or [SENTINEL], jnp.int32)
    got = lsh_retrieve_topc(starts, lens, extra, ids_flat, exclude,
                            C=C, cap=cap, interpret=True)
    want = lsh_retrieve_topc_ref(starts, lens, extra, ids_flat, exclude,
                                 C=C, cap=cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tail", [False, True])
def test_kernel_property_unique_subset_excluded(indexed, indexed_tail, tail):
    """Emitted ids are duplicate-free, drawn only from the probed windows
    ∪ tail extras, never excluded, SENTINEL-padded after an exhausted
    pool — and when the unique pool fits in C, it is covered exactly."""
    sp, cfg, sigs, index = indexed_tail if tail else indexed
    starts, lens, extra, ids_flat = _kernel_inputs(
        sp, index, B=16, n_seeds=4, cap=8, tail=tail)
    exclude = jnp.asarray([2, 5, 41], jnp.int32)
    C = 64
    got = np.asarray(lsh_retrieve_topc(starts, lens, extra, ids_flat,
                                       exclude, C=C, cap=8, interpret=True))
    pools = _pool_sets(starts, lens, extra, ids_flat)
    for u in range(16):
        ids = got[u][got[u] != SENTINEL]
        assert len(ids) == len(set(ids)), "duplicate candidate"
        want = pools[u] - {2, 5, 41}
        assert set(ids) <= want
        assert len(ids) == min(C, len(want)), "unique pool not covered"
        k = len(ids)
        assert np.all(got[u][k:] == SENTINEL), "padding must be trailing"


@pytest.mark.parametrize("tail", [False, True])
def test_retrieve_candidates_impls_agree_and_reserve_popular(
        indexed, indexed_tail, tail):
    """`ops.retrieve_candidates` pallas(interpret) ≡ ref, with the
    popularity shortlist in reserved trailing slots and excluded from
    the walked core (in-kernel, not via a second dedup)."""
    sp, cfg, sigs, index = indexed_tail if tail else indexed
    users = jnp.arange(12, dtype=jnp.int32)
    popular = jnp.asarray([2, 11, 17], jnp.int32)
    kw = dict(n_seeds=4, cap=8, C=48, popular=popular, window=32,
              tail_scan=tail, interpret=True)
    a = np.asarray(retrieve_candidates(index, sp, users, impl="pallas", **kw))
    b = np.asarray(retrieve_candidates(index, sp, users, impl="ref", **kw))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (12, 48)
    np.testing.assert_array_equal(a[:, 45:],
                                  np.broadcast_to([2, 11, 17], (12, 3)))
    core = a[:, :45]
    assert not np.isin(core, [2, 11, 17]).any(), "shortlist id in core"
    for u in range(12):
        v = core[u][core[u] != SENTINEL]
        assert len(v) == len(set(v))


# ------------------------------------------------- walk-path components

@pytest.mark.parametrize("n_seeds", [3, 4, 5, 8])   # 3, 5 hit the pad path
def test_window_descriptors_match_bruteforce(indexed, n_seeds):
    """Merged intervals cover exactly the union of per-seed bucket
    windows, and are disjoint within each band (counts sum to the union
    size).  Non-power-of-two seed counts exercise the bitonic pad."""
    sp, cfg, sigs, index = indexed
    cap, B = 8, 16
    users = jnp.arange(B, dtype=jnp.int32)
    seeds = seed_items(sp, users, n_seeds=n_seeds, window=32)
    starts, counts = window_descriptors(index, seeds, cap=cap)
    st, cnt = np.asarray(starts), np.asarray(counts)
    q, Nn = index.q, index.n_base
    slot_of = np.asarray(index.slot_of).reshape(q, -1)
    lo_a = np.asarray(index.bucket_lo).reshape(q, -1)
    hi_a = np.asarray(index.bucket_hi).reshape(q, -1)
    sd = np.asarray(seeds)
    for u in range(B):
        for g in range(q):
            want = set()
            for s in sd[u]:
                if s == SENTINEL or s < 0 or s >= Nn:
                    continue
                slot = int(slot_of[g, s])
                lo, hi = int(lo_a[g, slot]), int(hi_a[g, slot])
                w0 = int(np.clip(slot - cap // 2, lo, max(hi - cap, lo)))
                w1 = min(w0 + cap, hi)
                want |= set(range(g * Nn + w0, g * Nn + w1))
            got, total = set(), 0
            for i in range(g * n_seeds, (g + 1) * n_seeds):
                got |= set(range(st[u, i], st[u, i] + cnt[u, i]))
                total += cnt[u, i]
            assert got == want, f"user {u} band {g}: interval union wrong"
            assert total == len(want), "overlapping intervals in a band"


def test_enumerate_windows_budget_and_truncation():
    starts = jnp.asarray([[5, 100, 40], [7, 0, 0]], jnp.int32)
    counts = jnp.asarray([[3, 4, 2], [2, 0, 0]], jnp.int32)
    pos = np.asarray(enumerate_windows(starts, counts, budget=6))
    # row 0 totals 9 > 6: truncated in interval order, mid-interval
    np.testing.assert_array_equal(pos[0], [5, 6, 7, 100, 101, 102])
    # row 1: zero-count intervals skipped, −1 past the total
    np.testing.assert_array_equal(pos[1], [7, 8, -1, -1, -1, -1])
    # generous budget: exact expansion, nothing dropped
    pos = np.asarray(enumerate_windows(starts, counts, budget=12))
    np.testing.assert_array_equal(
        pos[0], [5, 6, 7, 100, 101, 102, 103, 40, 41, -1, -1, -1])


def test_tail_hits_static_prefix_slice(indexed_tail):
    """k-restricted scan sees every resident hit (the tail fills strictly
    in insertion order, so the prefix is the whole occupancy) and shrinks
    the output width; the full buffer past `tail_fill` is all misses."""
    sp, cfg, sigs, index = indexed_tail
    users = jnp.arange(24, dtype=jnp.int32)
    seeds = seed_items(sp, users, n_seeds=4, window=32)
    full = np.asarray(tail_hits(index, seeds))            # k=0 → whole buffer
    part = np.asarray(tail_hits(index, seeds, k=16))
    assert full.shape == (24, index.tail_cap) and part.shape == (24, 16)
    assert np.all(full[:, index.tail_fill:] == SENTINEL)
    for u in range(24):
        assert (set(part[u][part[u] != SENTINEL])
                == set(full[u][full[u] != SENTINEL]))
    # the clones collide with their sources: a user seeded on item 0
    # must see clone id N in its tail hits
    hit_rows = [u for u in range(24) if 0 in set(np.asarray(seeds)[u])]
    assert hit_rows, "fixture lost its seeded-on-item-0 users"
    for u in hit_rows:
        assert sp.N in set(part[u]), "clone unreachable through the tail"


def test_select_topn_masked_matches_dedup_oracle():
    """Duplicate-masked argmax selection ≡ numpy dedup-then-sort, across
    random pools with heavy duplication, SENTINEL slots, and rows holding
    fewer distinct ids than topn (exhaustion → SENTINEL fill)."""
    rng = np.random.default_rng(3)
    for trial in range(20):
        B = int(rng.integers(1, 6))
        W = int(rng.integers(4, 40))
        topn = int(rng.integers(1, 8))
        cand = rng.integers(0, 12, (B, W)).astype(np.int32)   # dense dups
        cand[rng.random((B, W)) < 0.25] = SENTINEL
        score_of = rng.permutation(12).astype(np.float32)     # distinct
        s = np.where(cand != SENTINEL, score_of[np.clip(cand, 0, 11)],
                     float(NEG)).astype(np.float32)
        gs, gi = _select_topn_masked(jnp.asarray(s), jnp.asarray(cand),
                                     topn=topn)
        gs, gi = np.asarray(gs), np.asarray(gi)
        for u in range(B):
            uniq = sorted({int(c) for c in cand[u] if c != SENTINEL},
                          key=lambda c: -score_of[c])[:topn]
            np.testing.assert_array_equal(gi[u, :len(uniq)], uniq)
            np.testing.assert_array_equal(gi[u, len(uniq):], SENTINEL)
            np.testing.assert_allclose(gs[u, :len(uniq)],
                                       score_of[uniq], rtol=1e-6)


@pytest.mark.parametrize("tail", [False, True])
def test_recommend_walked_matches_dedup_then_score(indexed, indexed_tail,
                                                   tail):
    """The fused walk path (duplicates deferred to selection) returns the
    same top-n id set and scores as dedup-first + exact scoring."""
    sp, cfg, sigs, index = indexed_tail if tail else indexed
    # params sized past the tail clones (ids N..N+4) so they score with
    # their own rows rather than the clipped last base row
    params = init_from_data(jax.random.PRNGKey(1), _sparse(N=sp.N + 5),
                            16, 8)
    planes = pack_serve_planes(params)
    users = jnp.arange(16, dtype=jnp.int32)
    popular = jnp.asarray([2, 11, 17, 40], jnp.int32)
    tail_k = 16 if tail else 0
    topn = 5
    gs, gi = recommend_walked(planes, index, sp, users, popular,
                              n_seeds=4, cap=8, budget=128, window=32,
                              tail_k=tail_k, topn=topn, tile_b=8)
    gs, gi = np.asarray(gs), np.asarray(gi)
    ids, seeds = walk_candidates(index, sp, users, n_seeds=4, cap=8,
                                 budget=128, window=32)
    pool = np.asarray(ids)
    if tail_k:
        pool = np.concatenate(
            [pool, np.asarray(tail_hits(index, seeds, k=tail_k))], axis=1)
    mu, b, bh = (np.asarray(params.mu), np.asarray(params.b),
                 np.asarray(params.bh))
    U, V = np.asarray(params.U), np.asarray(params.V)
    for u in range(16):
        cand = sorted(({int(c) for c in pool[u] if c != SENTINEL}
                       | {2, 11, 17, 40}))
        exact = (mu + b[u] + bh[cand] + V[cand] @ U[u])
        order = np.argsort(-exact)[:topn]
        want_ids = [cand[j] for j in order]
        assert set(gi[u]) - {SENTINEL} <= set(cand)
        np.testing.assert_array_equal(gi[u], want_ids)
        np.testing.assert_allclose(gs[u], exact[order], rtol=1e-4,
                                   atol=1e-4)


# ------------------------------------------------------------- routing

def test_route_decision_and_full_fallback(indexed):
    """Small-catalog routing: auto threshold is 48·C, the verdict is
    reported even when disabled, and a routed service serves exact
    full-scan results."""
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    base = ServeConfig(topn=5, micro_batch=8, C=48, n_seeds=4, cap=8,
                       n_popular=0)

    off = RecsysService(params, index, sp, base)
    rd = off.route_decision()
    assert not rd["enabled"] and rd["threshold"] == 48 * 48
    assert rd["decision"] == "full", "verdict must report even when off"

    auto = RecsysService(params, index, sp,
                         dataclasses.replace(base, route_full_below=-1))
    rd = auto.route_decision()
    assert rd["enabled"] and rd["n_items"] == sp.N
    assert rd["decision"] == "full"
    users = np.arange(8, dtype=np.int32)
    auto.submit(users); auto.flush()
    _, s_r, i_r = auto.take_results()[0]
    s_f, i_f = full_topn(params, jnp.asarray(users), topn=5)
    np.testing.assert_array_equal(i_r, np.asarray(i_f))
    np.testing.assert_allclose(s_r, np.asarray(s_f), rtol=1e-5, atol=1e-5)
    assert auto.stats()["route"]["decision"] == "full"

    tight = RecsysService(params, index, sp,
                          dataclasses.replace(base, route_full_below=10))
    assert tight.route_decision()["decision"] == "candidate"
