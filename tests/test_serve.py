"""repro.serve: bucketed index, retrieval, candidate-score kernel, service."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simlsh, topk
from repro.core.model import (Params, init_from_data, pack_serve_planes,
                              unpack_serve_planes)
from repro.core.simlsh import SimLSHConfig
from repro.data.sparse import from_coo
from repro.kernels.candidate_score.kernel import NEG, candidate_score_topn
from repro.kernels.candidate_score.ops import score_candidates
from repro.kernels.candidate_score.ref import candidate_score_topn_ref
from repro.serve import (RecsysService, ServeConfig, build_index,
                         compact_pool, dedup_candidates, insert,
                         lookup_items, lookup_signatures, rebuild,
                         retrieve_for_items, retrieve_for_users, seed_items)

SENTINEL = topk.SENTINEL
RNG = np.random.default_rng(0)


def _dup_matrix(M=200, half=30, seed=0):
    """Matrix whose column c+half duplicates column c exactly."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(M), 5).astype(np.int32)
    cols = rng.integers(0, half, M * 5).astype(np.int32)
    vals = rng.integers(1, 6, M * 5).astype(np.float32)
    rows2 = np.concatenate([rows, rows])
    cols2 = np.concatenate([cols, cols + half])
    vals2 = np.concatenate([vals, vals])
    keys = rows2.astype(np.int64) * (2 * half) + cols2
    _, uniq = np.unique(keys, return_index=True)
    return from_coo(rows2[uniq], cols2[uniq], vals2[uniq], (M, 2 * half))


@pytest.fixture(scope="module")
def indexed():
    sp = _dup_matrix()
    cfg = SimLSHConfig(G=8, p=2, q=8)
    sigs = simlsh.encode(sp, cfg, jax.random.PRNGKey(0))
    return sp, cfg, sigs, build_index(sigs, tail_cap=32)


# ---------------------------------------------------------------- index

def test_bucket_membership_roundtrip_vs_band_candidates(indexed):
    """Index mates = same-signature items, consistent with band_candidates."""
    sp, cfg, sigs, index = indexed
    N = sp.N
    cap = 8
    ids = jnp.arange(N, dtype=jnp.int32)
    mates = np.asarray(lookup_items(index, ids, cap=cap,
                                    include_tail=False)).reshape(N, cfg.q, cap)
    sigs_np = np.asarray(sigs)
    bc = np.asarray(jax.vmap(
        lambda s: topk.band_candidates(s, band_cap=cap))(sigs))   # [q, N, cap]
    for b in range(cfg.q):
        bucket_size = {s: c for s, c in
                       zip(*np.unique(sigs_np[b], return_counts=True))}
        for j in range(N):
            got = set(mates[j, b][mates[j, b] != SENTINEL])
            # membership: every mate shares the band signature
            assert all(sigs_np[b, m] == sigs_np[b, j] for m in got)
            assert j in got  # the item itself is always a bucket member
            # small buckets: exact agreement with the sort-based path
            if bucket_size[sigs_np[b, j]] <= cap // 2:
                ref = set(bc[b, j][bc[b, j] != SENTINEL]) | {j}
                assert got == ref


def test_lookup_signatures_finds_exact_buckets(indexed):
    sp, cfg, sigs, index = indexed
    qsigs = jnp.asarray(np.asarray(sigs)[:, :16].T)               # [16, q]
    cand = np.asarray(lookup_signatures(index, qsigs, cap=8, n_probe=2))
    sigs_np = np.asarray(sigs)
    for i in range(16):
        got = cand[i][cand[i] != SENTINEL]
        assert i in got  # probing with item i's own signatures finds i


def test_retrieval_recall_vs_bruteforce_cosine():
    """Candidates of an item must cover its brute-force cosine top-K on a
    matrix with planted column clusters (same-group columns share raters)."""
    rng = np.random.default_rng(0)
    n_groups, ipg, upg, deg = 12, 10, 24, 16     # N=120 items, M=288 users
    N, M = n_groups * ipg, n_groups * upg
    cols = np.arange(N, dtype=np.int32).repeat(deg)
    pick = np.argsort(rng.random((N, upg)), axis=1)[:, :deg]
    rows = (pick + (np.arange(N) // ipg)[:, None] * upg).reshape(-1)
    vals = rng.uniform(3, 5, rows.shape[0]).astype(np.float32)
    sp = from_coo(rows.astype(np.int32), cols, vals, (M, N))

    dense = np.zeros(sp.shape, np.float32)
    dense[np.asarray(sp.rows), np.asarray(sp.cols)] = np.asarray(sp.vals)
    norm = dense / np.maximum(np.linalg.norm(dense, axis=0, keepdims=True),
                              1e-9)
    cos = norm.T @ norm
    np.fill_diagonal(cos, -1.0)
    K = 3
    exact = np.argsort(-cos, axis=1)[:, :K]

    cfg = SimLSHConfig(G=8, p=1, q=12)
    recalls = []
    for seed in range(8):      # one hash key's recall spreads ≈ ±0.07
        sigs = simlsh.encode(sp, cfg, jax.random.PRNGKey(seed))
        index = build_index(sigs, tail_cap=32)
        cand = np.asarray(retrieve_for_items(
            index, jnp.arange(N, dtype=jnp.int32), cap=8, C=32))
        hits = sum(len(set(cand[j][cand[j] != SENTINEL]) & set(exact[j]))
                   for j in range(N))
        recalls.append(hits / (N * K))
    recall = float(np.mean(recalls))
    # C=32 of 120 items → chance recall ≈ 0.27; demand far better
    assert recall >= 0.6, f"mean recall@{K} vs cosine = {recall:.3f}"


def test_retrieval_always_finds_duplicate_partner(indexed):
    """Exact duplicate columns collide in every band → always retrieved."""
    sp, cfg, sigs, index = indexed
    cand = np.asarray(retrieve_for_items(
        index, jnp.arange(sp.N, dtype=jnp.int32), cap=8, C=64))
    half = sp.N // 2
    partners = (np.arange(sp.N) + half) % sp.N
    dup_hits = np.mean([partners[j] in set(cand[j]) for j in range(sp.N)])
    assert dup_hits == 1.0


def test_insert_then_lookup_and_rebuild(indexed):
    sp, cfg, sigs, index = indexed
    N = sp.N
    # clone three existing items into the tail
    src = jnp.asarray([0, 5, 9], jnp.int32)
    new_ids = jnp.asarray([N, N + 1, N + 2], jnp.int32)
    idx2 = insert(index, sigs[:, np.asarray(src)], new_ids)
    assert idx2.n_items == N + 3

    mates = np.asarray(lookup_items(idx2, src, cap=8))
    for r, nid in enumerate(np.asarray(new_ids)):
        assert nid in mates[r], "tail item not reachable from its bucket"
    # tail item as the query finds its base-bucket mates
    back = np.asarray(lookup_items(idx2, new_ids, cap=8))
    for r, s in enumerate(np.asarray(src)):
        assert s in back[r]

    # rebuild folds the tail into the sorted core; membership is preserved
    full_sigs = jnp.concatenate([sigs, sigs[:, np.asarray(src)]], axis=1)
    idx3 = rebuild(idx2, full_sigs)
    assert int(idx3.tail_len) == 0
    mates3 = np.asarray(lookup_items(idx3, src, cap=8, include_tail=False))
    for r, nid in enumerate(np.asarray(new_ids)):
        assert nid in mates3[r]


def test_insert_overflow_raises(indexed):
    sp, cfg, sigs, index = indexed
    with pytest.raises(ValueError, match="tail overflow"):
        insert(index, jnp.tile(sigs[:, :1], (1, 33)),
               jnp.arange(sp.N, sp.N + 33, dtype=jnp.int32))


# ---------------------------------------------------------------- retrieval

def test_dedup_candidates_unique_and_excludes():
    cands = jnp.asarray([[3, 1, 3, SENTINEL, 1, 7, 2, 2],
                         [5, 5, 5, 5, 5, 5, 5, 5]], jnp.int32)
    out = np.asarray(dedup_candidates(cands, C=6))
    assert sorted(out[0]) == [1, 2, 3, 7, SENTINEL, SENTINEL]
    assert sorted(out[1]) == [5] + [SENTINEL] * 5
    assert np.all(out[0][4:] == SENTINEL), "padding must sort last"
    out = np.asarray(dedup_candidates(
        cands, C=6, exclude_sorted=jnp.asarray([2, 5], jnp.int32)))
    assert sorted(out[0]) == [1, 3, 7, SENTINEL, SENTINEL, SENTINEL]
    assert list(out[1]) == [SENTINEL] * 6


def test_dedup_truncation_not_biased_against_high_ids():
    # overflow truncation must not systematically evict the largest ids
    # (newly ingested items always have the highest ids)
    row = jnp.arange(64, dtype=jnp.int32)[None, :]
    out = np.asarray(dedup_candidates(row, C=16))[0]
    kept = out[out != SENTINEL]
    assert len(kept) == 16
    assert (kept >= 48).any(), "top-quartile ids entirely evicted"


def test_dedup_property_unique_set_and_hashed_truncation():
    """Property sweep: (a) when a row has ≤ C unique ids the output is
    *exactly* the unique set (minus exclusions); (b) on overflow the kept
    ids are the C smallest under the invertible hash — the unbiased
    truncation order — and are always a duplicate-free subset."""
    _hash = lambda x: (x.astype(np.int64) * np.uint32(2654435761)) % (1 << 30)
    rng = np.random.default_rng(7)
    for trial in range(25):
        B = int(rng.integers(1, 5))
        L = int(rng.integers(1, 48))
        C = int(rng.integers(1, 40))
        ids = rng.integers(0, 60, (B, L)).astype(np.int32)
        ids[rng.random((B, L)) < 0.3] = SENTINEL
        excl = np.unique(rng.integers(0, 60, 4).astype(np.int32)) \
            if trial % 2 else None
        out = np.asarray(dedup_candidates(
            jnp.asarray(ids), C=C,
            exclude_sorted=jnp.asarray(excl) if excl is not None else None))
        assert out.shape == (B, C)
        for b in range(B):
            want = set(ids[b][ids[b] != SENTINEL])
            if excl is not None:
                want -= set(excl)
            got = out[b][out[b] != SENTINEL]
            assert len(got) == len(set(got)), "duplicates in dedup output"
            if len(want) <= C:
                assert set(got) == want, f"unique set not preserved (b={b})"
            else:
                assert len(got) == C
                kept = sorted(want, key=lambda x: _hash(np.int32(x)))[:C]
                assert set(got) == set(kept), "not the hash-order prefix"


def test_compact_pool_preserves_order_and_drops_sentinels():
    pool = jnp.asarray([[SENTINEL, 4, SENTINEL, 9, 2, SENTINEL, 7, 1],
                        [SENTINEL] * 8], jnp.int32)
    out = np.asarray(compact_pool(pool, width=5))
    assert list(out[0]) == [4, 9, 2, 7, 1]
    assert list(out[1]) == [SENTINEL] * 5
    # overflow drops the tail of the row, never reorders the kept prefix
    out = np.asarray(compact_pool(pool, width=3))
    assert list(out[0]) == [4, 9, 2]


def test_fold_prefix_runs_merges_pairs():
    from repro.serve.retrieve import _fold_prefix_runs
    S = SENTINEL
    runs = jnp.asarray([[[1, 2, S, S], [3, S, S, S]],
                        [[S, S, S, S], [4, 5, 6, 7]]], jnp.int32)
    out = np.asarray(_fold_prefix_runs(runs))        # cap=4 → width 6
    assert out.shape == (2, 1, 6)
    assert list(out[0, 0]) == [1, 2, 3, S, S, S]
    assert list(out[1, 0]) == [4, 5, 6, 7, S, S]
    # overflow: 4+4 survivors into 6 slots → right run's tail dropped
    full = jnp.asarray([[[1, 2, 3, 4], [5, 6, 7, 8]]], jnp.int32)
    assert list(np.asarray(_fold_prefix_runs(full))[0, 0]) == [1, 2, 3, 4, 5, 6]
    # odd run counts pass the last run through (padded to the fold width)
    odd = jnp.asarray([[[1, 2, S, S], [3, S, S, S], [9, S, S, S]]], jnp.int32)
    out = np.asarray(_fold_prefix_runs(odd))
    assert out.shape == (1, 2, 6) and list(out[0, 1]) == [9, S, S, S, S, S]


def test_retrieve_pool_width_keeps_popular_and_uniqueness(indexed):
    sp, cfg, sigs, index = indexed
    users = jnp.arange(16, dtype=jnp.int32)
    popular = jnp.asarray([2, 11, 17], jnp.int32)
    cand = np.asarray(retrieve_for_users(
        index, sp, users, n_seeds=4, cap=8, C=32, popular=popular,
        pool_width=64))
    assert cand.shape == (16, 32)
    for u in range(16):
        v = cand[u][cand[u] != SENTINEL]
        assert len(v) == len(set(v)), "duplicate candidates"
        assert {2, 11, 17} <= set(v), "popularity shortlist not reserved"


def test_seed_items_are_top_rated(indexed):
    sp, *_ = indexed
    users = jnp.arange(8, dtype=jnp.int32)
    seeds = np.asarray(seed_items(sp, users, n_seeds=4, window=32))
    dense = np.zeros(sp.shape, np.float32)
    dense[np.asarray(sp.rows), np.asarray(sp.cols)] = np.asarray(sp.vals)
    for u in range(8):
        s = seeds[u][seeds[u] != SENTINEL]
        assert len(s) > 0
        rated = dense[u][s]
        assert np.all(rated > 0), "seed item the user never rated"
        assert rated.min() >= np.sort(dense[u][dense[u] > 0])[::-1][
            :len(s)].min() - 1e-6


def test_retrieve_for_users_shapes_and_popular(indexed):
    sp, cfg, sigs, index = indexed
    users = jnp.arange(16, dtype=jnp.int32)
    popular = jnp.asarray([2, 11, 17], jnp.int32)
    cand = np.asarray(retrieve_for_users(
        index, sp, users, n_seeds=4, cap=8, C=32, popular=popular))
    assert cand.shape == (16, 32)
    for u in range(16):
        v = cand[u][cand[u] != SENTINEL]
        assert len(v) == len(set(v)), "duplicate candidates"
        assert {2, 11, 17} <= set(v), "popularity shortlist not reserved"


# ---------------------------------------------------------------- kernel


def _plane_args(B, C, F, N, rng, mask_p=0.7):
    """Random serve-plane scorer operands: urow [B, F+1] (μ+b folded in),
    plane [N, F+1], cand ids [B, C] (pre-clipped), mask [B, C]."""
    a = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    urow, plane = a(B, F + 1), a(N, F + 1)
    cand = jnp.asarray(rng.integers(0, N, (B, C)).astype(np.int32))
    mask = jnp.asarray((rng.random((B, C)) < mask_p).astype(np.float32))
    return urow, plane, cand, mask


@pytest.mark.parametrize("B,C,F,topn,tile", [
    (32, 64, 16, 10, 8), (7, 33, 8, 5, 16), (64, 128, 32, 1, 32)])
def test_candidate_score_kernel_matches_ref(B, C, F, topn, tile):
    """In-kernel gather path (interpret) ≡ tiled-scan jnp ref."""
    urow, plane, cand, mask = _plane_args(B, C, F, 200,
                                          np.random.default_rng(B * 3 + C))
    s1, i1 = candidate_score_topn(urow, plane, cand, mask, topn=topn,
                                  tile_b=tile, interpret=True)
    s2, i2 = candidate_score_topn_ref(urow, plane, cand, mask, topn=topn,
                                      tile_b=tile)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_candidate_score_kernel_all_masked_rows():
    urow, plane, cand, _ = _plane_args(9, 16, 8, 64, np.random.default_rng(5))
    mask = jnp.zeros((9, 16), jnp.float32)
    s1, i1 = candidate_score_topn(urow, plane, cand, mask, topn=4, tile_b=4,
                                  interpret=True)
    s2, i2 = candidate_score_topn_ref(urow, plane, cand, mask, topn=4,
                                      tile_b=4)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def _pr1_cube_scorer(params, user_ids, cand, *, topn):
    """The PR 1 scorer math — XLA-gathered [B, C, F] cube + `top_k` — as
    the old-vs-new parity oracle (same first-index tie rule)."""
    safe = jnp.clip(cand, 0, params.V.shape[0] - 1)
    mask = cand != SENTINEL
    s = (jnp.einsum("bf,bcf->bc", params.U[user_ids], params.V[safe])
         + params.bh[safe] + (params.mu + params.b[user_ids])[:, None])
    scores, idx = jax.lax.top_k(jnp.where(mask, s, NEG), topn)
    items = jnp.take_along_axis(cand, idx, axis=1)
    return scores, jnp.where(scores > NEG, items, SENTINEL)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("C,topn,tile", [(32, 5, 8), (48, 10, 16), (24, 3, 4)])
def test_scorer_matches_pr1_cube_scorer(indexed, impl, C, topn, tile):
    """New plane scorer ≡ the old cube scorer on identical candidate sets,
    across tile_b/C/topn sweeps and both impls (ISSUE 5 parity gate)."""
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    planes = pack_serve_planes(params)
    users = jnp.arange(24, dtype=jnp.int32)
    cand = retrieve_for_users(index, sp, users, n_seeds=4, cap=8, C=C)
    s_new, i_new = score_candidates(planes, users, cand, topn=topn,
                                    tile_b=tile, impl=impl, interpret=True)
    s_old, i_old = _pr1_cube_scorer(params, users, cand, topn=topn)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_old),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_new), np.asarray(i_old))
    # returned items must come from the candidate set
    c = np.asarray(cand)
    for u in range(24):
        got = np.asarray(i_new[u])
        assert set(got[got != SENTINEL]) <= set(c[u])


def test_score_candidates_accepts_params_and_planes(indexed):
    """`Params` is packed on the fly — same result as prebuilt planes."""
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    users = jnp.arange(8, dtype=jnp.int32)
    cand = retrieve_for_users(index, sp, users, n_seeds=4, cap=8, C=32)
    s1, i1 = score_candidates(params, users, cand, topn=5, impl="ref",
                              interpret=True)
    s2, i2 = score_candidates(pack_serve_planes(params), users, cand,
                              topn=5, impl="ref", interpret=True)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_serve_planes_roundtrip(indexed):
    sp, *_ = indexed
    params = init_from_data(jax.random.PRNGKey(2), sp, 16, 8)
    back = unpack_serve_planes(pack_serve_planes(params))
    for f in ("U", "V", "b", "bh", "mu"):
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(params, f)))


def test_scorer_hlo_has_no_candidate_cube():
    """ISSUE 5 acceptance: no gather in the scorer's HLO produces a
    B×C×F (or B×C×(F+1)) intermediate — only the tile-sized one."""
    B, C, F, N, tile = 64, 96, 24, 4000, 8
    rng = np.random.default_rng(0)
    planes_args = _plane_args(B, C, F, N, rng)
    users = jnp.arange(B, dtype=jnp.int32)
    params = Params(U=planes_args[1][:, :F], V=planes_args[1][:, :F],
                    b=jnp.zeros((N,)), bh=planes_args[1][:, F],
                    W=jnp.zeros((N, 0)), C=jnp.zeros((N, 0)),
                    mu=jnp.asarray(0.0))
    planes = pack_serve_planes(params)
    cand = planes_args[2]
    for impl in ("ref", "pallas"):
        txt = jax.jit(
            lambda p, u, c, impl=impl: score_candidates(
                p, u, c, topn=10, tile_b=tile, interpret=True, impl=impl)
        ).lower(planes, users[:B], cand).as_text()
        for bad in (f"{B}x{C}x{F}xf32", f"{B}x{C}x{F + 1}xf32"):
            assert bad not in txt, f"candidate cube {bad} in {impl} HLO"
    # the check looks at real lowered text: the ref's *tile* gather is there
    txt = jax.jit(
        lambda p, u, c: score_candidates(p, u, c, topn=10, tile_b=tile,
                                         interpret=True, impl="ref")
    ).lower(planes, users[:B], cand).as_text()
    assert f"{tile}x{C}x{F + 1}xf32" in txt


# ---------------------------------------------------------------- service

def test_service_candidate_matches_full_on_candidates(indexed):
    """Candidate-mode top-1 score equals the full-mode score of that item."""
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    scfg = ServeConfig(topn=5, micro_batch=16, C=48, n_seeds=4, cap=8,
                       n_popular=8)
    svc = RecsysService(params, index, sp, scfg).warmup()
    full = RecsysService(params, index, sp,
                         dataclasses.replace(scfg, mode="full")).warmup()
    users = np.arange(16, dtype=np.int32)
    svc.submit(users); svc.flush()
    full.submit(users); full.flush()
    _, s_c, i_c = svc.take_results()[0]
    _, s_f, i_f = full.take_results()[0]
    # every candidate-mode score must equal the exact score of that item
    exact = (np.asarray(params.mu) + np.asarray(params.b)[users][:, None]
             + np.asarray(params.bh)[i_c]
             + np.einsum("bf,bnf->bn", np.asarray(params.U)[users],
                         np.asarray(params.V)[i_c]))
    np.testing.assert_allclose(s_c, exact, rtol=1e-4, atol=1e-4)
    st = svc.stats()
    assert st["users"] == 16 and st["batches"] == 1


def test_service_micro_batching_and_partial_flush(indexed):
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    scfg = ServeConfig(topn=3, micro_batch=8, C=32, n_seeds=4, cap=8,
                       n_popular=0)
    svc = RecsysService(params, index, sp, scfg)
    svc.submit(np.arange(5));   assert svc.stats()["batches"] == 0
    svc.submit(np.arange(5));   assert svc.stats()["batches"] == 1
    svc.flush()
    st = svc.stats()
    assert st["users"] == 10 and st["batches"] == 2
    res = svc.take_results()
    assert sum(r[0].shape[0] for r in res) == 10
    assert all(r[2].shape[1] == 3 for r in res)


def test_pipelined_flush_ordering_maps_results_to_users(indexed):
    """Dispatch-ahead flushes must hand each user their own result, in
    flush order, with the padded final batch stripped correctly.  Params
    are planted so user u's exact top-1 item is u itself (U = 5·I,
    V = I): any cross-flush or cross-row mixup is immediately visible."""
    sp, cfg, sigs, index = indexed
    M = N = F = 16
    eye = jnp.eye(M, dtype=jnp.float32)
    params = Params(U=5.0 * eye, V=eye, b=jnp.zeros((M,)),
                    bh=jnp.zeros((N,)), W=jnp.zeros((N, 1)),
                    C=jnp.zeros((N, 1)), mu=jnp.asarray(0.0))
    scfg = ServeConfig(mode="full", topn=3, micro_batch=M, n_popular=0)
    svc = RecsysService(params, index, sp, scfg).warmup()
    rng = np.random.default_rng(11)
    users = rng.integers(0, M, 3 * M + 5).astype(np.int32)
    for chunk in np.split(users, [7, 20, 29, 41]):   # ragged submits
        svc.submit(chunk)
    assert svc.stats()["batches"] == 3               # dispatched, not synced
    svc.flush()
    res = svc.take_results()
    assert len(res) == 4 and res[-1][0].shape[0] == 5   # padded final batch
    got_users = np.concatenate([r[0] for r in res])
    np.testing.assert_array_equal(got_users, users)     # flush order kept
    for r_users, _, r_items in res:
        np.testing.assert_array_equal(r_items[:, 0], r_users)
    st = svc.stats()
    assert st["users"] == users.shape[0] and st["batches"] == 4
    assert st["qps"] > 0 and st["p95_ms"] >= st["p50_ms"]


def test_pipelined_flush_ordering_candidate_mode(indexed):
    """Same per-user identity check through the fused candidate pipeline:
    every top-1 score must equal that item's exact full score for *that*
    user — a result swapped across in-flight flushes would not."""
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    scfg = ServeConfig(topn=3, micro_batch=8, C=32, n_seeds=4, cap=8,
                       n_popular=0)
    svc = RecsysService(params, index, sp, scfg).warmup()
    users = np.arange(24, dtype=np.int32)
    for u in users:          # one-at-a-time submits → 3 pipelined flushes
        svc.submit(u)
    svc.flush()
    res = svc.take_results()
    assert [r[0].shape[0] for r in res] == [8, 8, 8]
    for r_users, r_scores, r_items in res:
        safe = np.clip(r_items, 0, sp.N - 1)
        exact = (np.asarray(params.mu) + np.asarray(params.b)[r_users][:, None]
                 + np.asarray(params.bh)[safe]
                 + np.einsum("bf,bnf->bn", np.asarray(params.U)[r_users],
                             np.asarray(params.V)[safe]))
        ok = r_items != SENTINEL
        np.testing.assert_allclose(r_scores[ok], exact[ok],
                                   rtol=1e-4, atol=1e-4)


def test_service_ingest_serves_new_items(indexed):
    sp, cfg, sigs, index = indexed
    params = init_from_data(jax.random.PRNGKey(1), sp, 16, 8)
    scfg = ServeConfig(topn=5, micro_batch=8, C=48, n_seeds=4, cap=8,
                       n_popular=0)
    svc = RecsysService(params, index, sp, scfg)
    # clone item 0's signature as a new item; it joins item 0's buckets
    svc.ingest(sigs[:, :1], jnp.asarray([sp.N], jnp.int32))
    assert svc.index.n_items == sp.N + 1
    cand = np.asarray(retrieve_for_items(
        svc.index, jnp.asarray([0], jnp.int32), cap=8, C=32))
    assert sp.N in cand[0]
