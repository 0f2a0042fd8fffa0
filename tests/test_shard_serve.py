"""Parity/property suite for the sharded serving tier (ISSUE 9).

Three families, all single-process (the 4-device end-to-end parity run
lives in tests/helpers/multidev_checks.py::check_sharded_serve):

* **merge_topn / tree reduce** — the per-user top-N merge must equal the
  exact top-N of the concatenated shard partials under random splits,
  ties, SENTINEL padding and users with fewer than N candidates, and the
  XOR-butterfly fold must converge every participant to that same answer
  (numpy `lexsort` oracle; hypothesis path when installed, shimmed by
  conftest otherwise).

* **sharded index invariants** — shard-local bucket membership
  round-trips to the single-device `build_index` buckets after the
  global→local remap, per-shard CSR invariants hold
  (`validate_sharded_index`), padding slots are inert.

* **shard-local walk** — owner-computes signature exchange sums to the
  true seed signatures, and the union of per-shard walks at
  truncation-free settings equals the single-device `walk_candidates`
  retrieval set.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import simlsh
from repro.core.topk import SENTINEL
from repro.data.sparse import from_coo
from repro.kernels.candidate_score.kernel import NEG
from repro.launch.mesh import serve_shard_count
from repro.resil import validate_index, validate_sharded_index
from repro.serve import (ServeConfig, build_index, build_sharded_index,
                         merge_topn, shard_bounds, shard_local_view,
                         shard_seed_sigs, shard_walk_local, signatures_of,
                         translate_local_ids, walk_candidates)
from repro.serve.index import _EMPTY_SIG
from repro.serve.retrieve import seed_items

TOPN = 8


# ---------------------------------------------------------------------------
# oracle + partial generators
# ---------------------------------------------------------------------------

def oracle_topn(scores: np.ndarray, ids: np.ndarray, topn: int):
    """Exact top-N of one user's candidate list under the serving total
    order (score desc, id asc); rows with < topn real entries padded
    with (NEG, SENTINEL) exactly like `_select_topn_masked`."""
    real = ids != SENTINEL
    s, i = scores[real], ids[real]
    order = np.lexsort((i, -s))[:topn]
    out_s = np.full(topn, NEG, np.float32)
    out_i = np.full(topn, SENTINEL, np.int32)
    out_s[:order.size] = s[order]
    out_i[:order.size] = i[order]
    return out_s, out_i


def random_partials(rng, *, B, D, topn, n_ids=200, tie_prob=0.0,
                    empty_prob=0.0):
    """D disjoint-id shard partials [B, topn] — the butterfly invariant
    (each candidate counted once) holds by construction, so every id
    appears in at most one shard."""
    sa, ia = [], []
    for _ in range(D):
        sa.append(np.full((B, topn), NEG, np.float32))
        ia.append(np.full((B, topn), SENTINEL, np.int32))
    for b in range(B):
        ids = rng.choice(n_ids, size=min(n_ids, D * topn), replace=False)
        scores = rng.normal(size=ids.size).astype(np.float32)
        if tie_prob:
            tied = rng.random(ids.size) < tie_prob
            scores[tied] = np.float32(0.5)
        take = rng.integers(0, topn + 1, D) if empty_prob else \
            np.full(D, topn)
        if empty_prob:
            take[rng.random(D) < empty_prob] = 0
        pos = 0
        for d in range(D):
            k = min(int(take[d]), ids.size - pos)
            if k <= 0:
                continue
            s, i = oracle_topn(scores[pos:pos + k], ids[pos:pos + k], topn)
            sa[d][b], ia[d][b] = s, i
            pos += k
    return sa, ia


def merged_oracle(sa, ia, topn):
    B = sa[0].shape[0]
    s = np.concatenate(sa, axis=1)
    i = np.concatenate(ia, axis=1)
    outs = [oracle_topn(s[b], i[b], topn) for b in range(B)]
    return (np.stack([o[0] for o in outs]), np.stack([o[1] for o in outs]))


def assert_topn_equal(got_s, got_i, ref_s, ref_i):
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    # ids must match exactly (the order is total: score desc, id asc)
    np.testing.assert_array_equal(got_i, ref_i)
    real = ref_i != SENTINEL
    np.testing.assert_allclose(got_s[real], ref_s[real], rtol=1e-6)
    assert np.all(got_s[~real] <= NEG)


# ---------------------------------------------------------------------------
# merge_topn: oracle equivalence, ties, padding, algebra
# ---------------------------------------------------------------------------

class TestMergeTopn:
    def test_two_shards_match_oracle(self):
        rng = np.random.default_rng(0)
        sa, ia = random_partials(rng, B=16, D=2, topn=TOPN)
        ms, mi = merge_topn(jnp.asarray(sa[0]), jnp.asarray(ia[0]),
                            jnp.asarray(sa[1]), jnp.asarray(ia[1]),
                            topn=TOPN)
        ref_s, ref_i = merged_oracle(sa, ia, TOPN)
        assert_topn_equal(ms, mi, ref_s, ref_i)

    def test_ties_break_by_lower_id(self):
        sa = jnp.asarray([[3.0, 1.0]]); ia = jnp.asarray([[7, 9]], jnp.int32)
        sb = jnp.asarray([[3.0, 3.0]]); ib = jnp.asarray([[2, 5]], jnp.int32)
        ms, mi = merge_topn(sa, ia, sb, ib, topn=3)
        np.testing.assert_array_equal(np.asarray(mi), [[2, 5, 7]])
        np.testing.assert_allclose(np.asarray(ms), [[3.0, 3.0, 3.0]])

    def test_all_tied_scores_sort_ids(self):
        rng = np.random.default_rng(1)
        sa, ia = random_partials(rng, B=8, D=2, topn=TOPN, tie_prob=1.0)
        ms, mi = merge_topn(jnp.asarray(sa[0]), jnp.asarray(ia[0]),
                            jnp.asarray(sa[1]), jnp.asarray(ia[1]),
                            topn=TOPN)
        ref_s, ref_i = merged_oracle(sa, ia, TOPN)
        assert_topn_equal(ms, mi, ref_s, ref_i)

    def test_sentinel_padded_shard_is_identity(self):
        rng = np.random.default_rng(2)
        sa, ia = random_partials(rng, B=8, D=1, topn=TOPN)
        pad_s = jnp.full((8, TOPN), NEG, jnp.float32)
        pad_i = jnp.full((8, TOPN), SENTINEL, jnp.int32)
        ms, mi = merge_topn(jnp.asarray(sa[0]), jnp.asarray(ia[0]),
                            pad_s, pad_i, topn=TOPN)
        assert_topn_equal(ms, mi, sa[0], ia[0])

    def test_fewer_than_topn_candidates_pad(self):
        sa = jnp.asarray([[4.0] + [NEG] * (TOPN - 1)])
        ia = jnp.asarray([[3] + [SENTINEL] * (TOPN - 1)], jnp.int32)
        sb = jnp.asarray([[2.0] + [NEG] * (TOPN - 1)])
        ib = jnp.asarray([[11] + [SENTINEL] * (TOPN - 1)], jnp.int32)
        ms, mi = merge_topn(sa, ia, sb, ib, topn=TOPN)
        np.testing.assert_array_equal(np.asarray(mi)[0, :2], [3, 11])
        assert np.all(np.asarray(mi)[0, 2:] == SENTINEL)
        assert np.all(np.asarray(ms)[0, 2:] <= NEG)

    def test_both_shards_empty(self):
        pad_s = jnp.full((4, TOPN), NEG, jnp.float32)
        pad_i = jnp.full((4, TOPN), SENTINEL, jnp.int32)
        ms, mi = merge_topn(pad_s, pad_i, pad_s, pad_i, topn=TOPN)
        assert np.all(np.asarray(mi) == SENTINEL)
        assert np.all(np.asarray(ms) <= NEG)

    def test_commutative(self):
        rng = np.random.default_rng(3)
        sa, ia = random_partials(rng, B=8, D=2, topn=TOPN, tie_prob=0.3)
        ab = merge_topn(jnp.asarray(sa[0]), jnp.asarray(ia[0]),
                        jnp.asarray(sa[1]), jnp.asarray(ia[1]), topn=TOPN)
        ba = merge_topn(jnp.asarray(sa[1]), jnp.asarray(ia[1]),
                        jnp.asarray(sa[0]), jnp.asarray(ia[0]), topn=TOPN)
        np.testing.assert_array_equal(np.asarray(ab[1]), np.asarray(ba[1]))
        np.testing.assert_allclose(np.asarray(ab[0]), np.asarray(ba[0]))

    def test_associative(self):
        rng = np.random.default_rng(4)
        sa, ia = random_partials(rng, B=8, D=3, topn=TOPN, tie_prob=0.2)
        j = [(jnp.asarray(s), jnp.asarray(i)) for s, i in zip(sa, ia)]
        left = merge_topn(*merge_topn(*j[0], *j[1], topn=TOPN), *j[2],
                          topn=TOPN)
        right = merge_topn(*j[0], *merge_topn(*j[1], *j[2], topn=TOPN),
                           topn=TOPN)
        np.testing.assert_array_equal(np.asarray(left[1]),
                                      np.asarray(right[1]))
        np.testing.assert_allclose(np.asarray(left[0]), np.asarray(right[0]))

    @pytest.mark.parametrize("D", [2, 4, 8])
    def test_butterfly_fold_matches_oracle(self, D):
        """The serving tree reduce: after log2(D) XOR-partner rounds every
        participant holds the exact top-N of all D partials."""
        rng = np.random.default_rng(D)
        sa, ia = random_partials(rng, B=8, D=D, topn=TOPN, tie_prob=0.2,
                                 empty_prob=0.2)
        parts = [(jnp.asarray(s), jnp.asarray(i)) for s, i in zip(sa, ia)]
        k = 1
        while k < D:
            parts = [merge_topn(*parts[d], *parts[d ^ k], topn=TOPN)
                     for d in range(D)]
            k *= 2
        ref_s, ref_i = merged_oracle(sa, ia, TOPN)
        for d in range(D):
            assert_topn_equal(parts[d][0], parts[d][1], ref_s, ref_i)

    # no deadline: the first example pays the merge's jit compile
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 10_000), st.integers(2, 6), st.integers(1, 12))
    def test_property_random_splits(self, seed, D, topn):
        rng = np.random.default_rng(seed)
        sa, ia = random_partials(rng, B=4, D=D, topn=topn, tie_prob=0.3,
                                 empty_prob=0.3)
        acc = (jnp.asarray(sa[0]), jnp.asarray(ia[0]))
        for d in range(1, D):
            acc = merge_topn(*acc, jnp.asarray(sa[d]), jnp.asarray(ia[d]),
                             topn=topn)
        ref_s, ref_i = merged_oracle(sa, ia, topn)
        assert_topn_equal(acc[0], acc[1], ref_s, ref_i)


# ---------------------------------------------------------------------------
# sharded index: bounds, CSR invariants, bucket round-trip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_catalog():
    rng = np.random.default_rng(0)
    M, N, deg = 200, 300, 8
    rows = np.repeat(np.arange(M), deg)
    cols = rng.integers(0, N, M * deg)
    vals = rng.uniform(1, 5, M * deg).astype(np.float32)
    order = np.lexsort((cols, rows))
    sp = from_coo(rows[order], cols[order], vals[order], (M, N))
    cfg = simlsh.SimLSHConfig(G=4, p=2, q=4)
    sigs = simlsh.encode(sp, cfg, jax.random.PRNGKey(0))
    counts = np.bincount(np.asarray(sp.cols), minlength=N)
    return sp, sigs, counts


@pytest.fixture(scope="module")
def sharded4(small_catalog):
    _, sigs, counts = small_catalog
    bounds = shard_bounds(counts, 4)
    return build_sharded_index(sigs, shards=4, bounds=bounds)


class TestShardedIndex:
    def test_shard_bounds_cover_and_monotone(self, small_catalog):
        _, _, counts = small_catalog
        for D in (1, 2, 4, 8):
            b = shard_bounds(counts, D)
            assert b[0] == 0 and b[-1] == counts.size
            assert np.all(np.diff(b) > 0)

    def test_shard_bounds_nnz_balanced(self, small_catalog):
        _, _, counts = small_catalog
        b = shard_bounds(counts, 4)
        per = [counts[b[d]:b[d + 1]].sum() for d in range(4)]
        naive = [counts[i * 75:(i + 1) * 75].sum() for i in range(4)]
        # balanced cuts must not be worse than the even split
        assert max(per) <= max(naive)

    def test_geometry(self, sharded4, small_catalog):
        _, sigs, _ = small_catalog
        assert sharded4.shards == 4
        assert sharded4.q == int(sigs.shape[0])
        assert sharded4.n_items == int(sigs.shape[1])
        nl = np.asarray(sharded4.n_local)
        assert nl.sum() == sharded4.n_items
        assert nl.max() == sharded4.block
        assert sharded4.sorted_sigs.shape == (4, sharded4.q, sharded4.block)

    def test_validate_sharded_index_clean(self, sharded4):
        assert validate_sharded_index(sharded4) == []

    def test_validate_index_dispatches_on_sharded(self, sharded4):
        assert validate_index(sharded4) == []

    def test_validate_sharded_index_catches_corruption(self, sharded4):
        bad = np.asarray(sharded4.sorted_ids).copy()
        bad[1, 0, :2] = bad[1, 0, 0]          # duplicate local id in band 0
        broken = dataclasses.replace(sharded4, sorted_ids=jnp.asarray(bad))
        probs = validate_sharded_index(broken)
        assert probs and any("shard 1" in p for p in probs)

    def test_validate_sharded_index_catches_bad_bounds(self, sharded4):
        bad = np.asarray(sharded4.bounds).copy()
        bad[1] = bad[2]                        # zero-width shard
        broken = dataclasses.replace(sharded4, bounds=jnp.asarray(bad))
        assert any("strictly increasing" in p
                   for p in validate_sharded_index(broken))

    def test_local_ids_partition_catalog(self, sharded4):
        bounds = np.asarray(sharded4.bounds)
        nl = np.asarray(sharded4.n_local)
        seen = []
        for d in range(4):
            ids = np.asarray(sharded4.sorted_ids[d, 0])
            real = ids[ids < nl[d]]            # padding local ids sort high
            assert np.array_equal(np.sort(real), np.arange(nl[d]))
            seen.append(real + bounds[d])
        got = np.sort(np.concatenate(seen))
        assert np.array_equal(got, np.arange(sharded4.n_items))

    def test_bucket_membership_roundtrips(self, sharded4, small_catalog):
        """Per band: an item's shard-local bucket (same signature, same
        shard) is exactly the single-device bucket ∩ the shard — the
        satellite's global→local round-trip property."""
        _, sigs, _ = small_catalog
        sigs = np.asarray(sigs)
        bounds = np.asarray(sharded4.bounds)
        nl = np.asarray(sharded4.n_local)
        for d in range(4):
            view = shard_local_view(sharded4, d)
            ss = np.asarray(view.sorted_sigs)
            si = np.asarray(view.sorted_ids)
            lo_ = np.asarray(view.bucket_lo)
            hi_ = np.asarray(view.bucket_hi)
            so = np.asarray(view.slot_of)
            for b in range(sharded4.q):
                for g in range(bounds[d], bounds[d + 1]):
                    local = g - bounds[d]
                    slot = so[b, local]
                    assert ss[b, slot] == sigs[b, g]
                    members = si[b, lo_[b, slot]:hi_[b, slot]]
                    members = members[members < nl[d]] + bounds[d]
                    ref = np.flatnonzero(sigs[b] == sigs[b, g])
                    ref = ref[(ref >= bounds[d]) & (ref < bounds[d + 1])]
                    assert np.array_equal(np.sort(members), ref), (d, b, g)

    def test_padding_slots_inert(self, sharded4):
        ss = np.asarray(sharded4.sorted_sigs)
        nl = np.asarray(sharded4.n_local)
        for d in range(4):
            n_pad = sharded4.block - nl[d]
            # every padded slot carries _EMPTY_SIG and sorts first
            assert np.all((ss[d] == int(_EMPTY_SIG)).sum(axis=1) == n_pad)
            if n_pad:
                assert np.all(ss[d, :, :n_pad] == int(_EMPTY_SIG))

    def test_single_shard_equals_plain_index(self, small_catalog):
        _, sigs, _ = small_catalog
        plain = build_index(sigs, tail_cap=0)
        one = build_sharded_index(sigs, shards=1)
        view = shard_local_view(one, 0)
        for f in ("sorted_sigs", "sorted_ids", "bucket_lo", "bucket_hi",
                  "slot_of"):
            np.testing.assert_array_equal(np.asarray(getattr(view, f)),
                                          np.asarray(getattr(plain, f)), f)

    def test_signatures_of_roundtrip(self, small_catalog):
        _, sigs, _ = small_catalog
        idx = build_index(sigs, tail_cap=0)
        np.testing.assert_array_equal(np.asarray(signatures_of(idx)),
                                      np.asarray(sigs))

    def test_build_guards(self, small_catalog):
        _, sigs, _ = small_catalog
        with pytest.raises(TypeError):
            build_sharded_index(sigs.astype(jnp.float32), shards=2)
        with pytest.raises(ValueError):
            build_sharded_index(sigs, shards=0)
        with pytest.raises(ValueError):
            build_sharded_index(sigs, shards=2,
                                bounds=np.asarray([0, 200, 150, 300]))
        with pytest.raises(ValueError):
            build_sharded_index(sigs, shards=2, bounds=np.asarray([0, 300]))


# ---------------------------------------------------------------------------
# shard-local walk: signature exchange + union parity vs single device
# ---------------------------------------------------------------------------

class TestShardWalk:
    # truncation-free settings: cap ≥ any bucket, budget ≥ q·block, so
    # both paths enumerate every bucket in full and parity is exact
    CAP, BUDGET = 512, 2048

    def test_seed_sig_exchange_sums_to_truth(self, small_catalog, sharded4):
        sp, sigs, _ = small_catalog
        users = jnp.arange(32, dtype=jnp.int32)
        seeds = seed_items(sp, users, n_seeds=4, window=32)
        bounds = np.asarray(sharded4.bounds)
        total = np.zeros((sharded4.q,) + seeds.shape, np.int64)
        for d in range(4):
            contrib = shard_seed_sigs(sharded4.sorted_sigs[d],
                                      sharded4.slot_of[d], seeds,
                                      int(bounds[d]),
                                      int(sharded4.n_local[d]))
            total += np.asarray(contrib, np.int64)
        sigs = np.asarray(sigs)
        seeds = np.asarray(seeds)
        valid = seeds != SENTINEL
        ref = sigs[:, np.where(valid, seeds, 0)]
        np.testing.assert_array_equal(total[:, valid], ref[:, valid])
        assert np.all(total[:, ~valid] == 0)

    def test_seed_sig_exchange_disjoint_owners(self, small_catalog,
                                               sharded4):
        """Each valid seed is owned by exactly one shard (its nonzero
        contribution), so the psum is an exchange, not an accumulation."""
        sp, sigs, _ = small_catalog
        users = jnp.arange(16, dtype=jnp.int32)
        seeds = seed_items(sp, users, n_seeds=4, window=32)
        bounds = np.asarray(sharded4.bounds)
        owners = np.zeros(seeds.shape, np.int32)
        for d in range(4):
            contrib = np.asarray(shard_seed_sigs(
                sharded4.sorted_sigs[d], sharded4.slot_of[d], seeds,
                int(bounds[d]), int(sharded4.n_local[d])))
            owners += np.any(contrib != 0, axis=0)
        valid = np.asarray(seeds) != SENTINEL
        # a signature can be legitimately all-zero, so owners ≤ 1 is the
        # invariant (0 only for all-zero-signature or invalid seeds)
        assert np.all(owners[valid] <= 1)
        assert np.all(owners[~valid] == 0)

    def _sharded_union(self, sharded4, sp, users, *, cap, budget,
                       n_seeds=4, window=32):
        seeds = seed_items(sp, users, n_seeds=n_seeds, window=window)
        bounds = np.asarray(sharded4.bounds)
        total = np.zeros((sharded4.q,) + seeds.shape, np.int32)
        for d in range(4):
            total += np.asarray(shard_seed_sigs(
                sharded4.sorted_sigs[d], sharded4.slot_of[d], seeds,
                int(bounds[d]), int(sharded4.n_local[d])))
        qsigs = jnp.where((np.asarray(seeds) != SENTINEL)[None],
                          jnp.asarray(total), _EMPTY_SIG)
        per_user = [set() for _ in range(users.shape[0])]
        for d in range(4):
            local = shard_walk_local(sharded4.sorted_sigs[d],
                                     sharded4.sorted_ids[d], qsigs,
                                     int(sharded4.n_local[d]),
                                     cap=cap, budget=budget)
            glob = np.asarray(translate_local_ids(local, int(bounds[d])))
            for u in range(users.shape[0]):
                per_user[u] |= set(glob[u][glob[u] != SENTINEL].tolist())
        return per_user, seeds

    def test_union_parity_with_single_device_walk(self, small_catalog,
                                                  sharded4):
        sp, sigs, _ = small_catalog
        idx = build_index(sigs, tail_cap=0)
        users = jnp.arange(48, dtype=jnp.int32)
        got, _ = self._sharded_union(sharded4, sp, users, cap=self.CAP,
                                     budget=self.BUDGET)
        ids, _ = walk_candidates(idx, sp, users, n_seeds=4, cap=self.CAP,
                                 budget=self.BUDGET, window=32)
        ids = np.asarray(ids)
        for u in range(users.shape[0]):
            ref = set(ids[u][ids[u] != SENTINEL].tolist())
            assert got[u] == ref, f"user {u}"

    def test_walk_never_emits_padding_or_foreign_ids(self, small_catalog,
                                                     sharded4):
        sp, _, _ = small_catalog
        users = jnp.arange(32, dtype=jnp.int32)
        bounds = np.asarray(sharded4.bounds)
        seeds = seed_items(sp, users, n_seeds=4, window=32)
        total = np.zeros((sharded4.q,) + seeds.shape, np.int32)
        for d in range(4):
            total += np.asarray(shard_seed_sigs(
                sharded4.sorted_sigs[d], sharded4.slot_of[d], seeds,
                int(bounds[d]), int(sharded4.n_local[d])))
        qsigs = jnp.where((np.asarray(seeds) != SENTINEL)[None],
                          jnp.asarray(total), _EMPTY_SIG)
        for d in range(4):
            local = np.asarray(shard_walk_local(
                sharded4.sorted_sigs[d], sharded4.sorted_ids[d], qsigs,
                int(sharded4.n_local[d]), cap=8, budget=64))
            real = local[local != SENTINEL]
            assert np.all((real >= 0) & (real < int(sharded4.n_local[d])))

    def test_empty_sig_probes_retrieve_nothing(self, sharded4):
        qsigs = jnp.full((sharded4.q, 4, 4), _EMPTY_SIG, jnp.int32)
        local = np.asarray(shard_walk_local(
            sharded4.sorted_sigs[0], sharded4.sorted_ids[0], qsigs,
            int(sharded4.n_local[0]), cap=8, budget=64))
        assert np.all(local == SENTINEL)

    def test_translate_local_ids(self):
        local = jnp.asarray([[0, 5, SENTINEL], [SENTINEL, 2, 1]], jnp.int32)
        out = np.asarray(translate_local_ids(local, 100))
        np.testing.assert_array_equal(
            out, [[100, 105, SENTINEL], [SENTINEL, 102, 101]])


# ---------------------------------------------------------------------------
# config / resolution
# ---------------------------------------------------------------------------

class TestShardConfig:
    def test_serve_shard_count_resolution(self):
        assert serve_shard_count(0) == 1
        assert serve_shard_count(1) == 1
        assert serve_shard_count("auto") >= 1    # largest pow2 ≤ devices
        with pytest.raises(ValueError):
            serve_shard_count(3)                  # not a power of two
        with pytest.raises(ValueError):
            serve_shard_count(2 * jax.device_count())   # exceeds devices

    def test_resolved_shard_budget(self):
        cfg = ServeConfig(band_budget=512)
        # auto: 2× the per-shard share of the single-device budget,
        # rounded up to a lane multiple, never below 64
        assert cfg.resolved_shard_budget(4) == 256
        assert cfg.resolved_shard_budget(16) == 64
        assert dataclasses.replace(
            cfg, shard_budget=96).resolved_shard_budget(4) == 96

    def test_sharded_service_is_read_only(self, small_catalog):
        """ingest on a sharded service must refuse (the satellite's
        read-only contract) — exercised via the state flag the flush
        path keys on, since >1 host device needs a subprocess."""
        from repro.core import model
        from repro.serve import RecsysService
        sp, sigs, _ = small_catalog
        idx = build_index(sigs, tail_cap=0)
        M, N = sp.shape
        params = model.init_params(jax.random.PRNGKey(0), M, N, 8, 4)
        svc = RecsysService(params, idx, sp,
                            ServeConfig(topn=4, micro_batch=8, n_seeds=4,
                                        cap=8, band_budget=64, n_popular=0,
                                        use_jk=False))
        assert svc._shard_state is None          # 1 device → oracle path
        svc._shard_state = (None, None, None, 2)
        with pytest.raises(NotImplementedError):
            svc.ingest(sigs[:, :1], jnp.asarray([N], jnp.int32))
        with pytest.raises(NotImplementedError):
            svc.ingest_online_update(object(), N)
