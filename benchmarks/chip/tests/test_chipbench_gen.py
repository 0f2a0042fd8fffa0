"""Statistics the benchmark's generators keep: shape, nnz, rating range,
zipf head share and planted-group structure; the same bits as the
generator's first version wherever that one was sound; distinct pairs
past 2³¹ cells and a Zipf draw that reaches every id."""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import gen  # noqa: E402

RATINGS = dict(M=500, N=300, nnz=20000, test_frac=0.1, zipf_a=1.2, rank=8,
               noise=0.35, groups=0, rmin=1.0, rmax=5.0)
SEED = 2**31 + 12345
ML10M = dict(RATINGS, M=27951, N=10677, nnz=200000)


# The generator's first version, frozen: the oracle of the sizes where its
# int32 pair keys and float32 Zipf CDF were sound (M·N < 2³¹, every id's
# share resolved).
def _frozen_zipf_cdf(n, a):
    p = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32) ** a
    return jnp.cumsum(p) / jnp.sum(p)


def _frozen_draw_pairs(key, M, N, take, a):
    ku, ki = jax.random.split(key)
    r = jnp.searchsorted(_frozen_zipf_cdf(M, a), jax.random.uniform(ku, (take,)))
    c = jnp.searchsorted(_frozen_zipf_cdf(N, a), jax.random.uniform(ki, (take,)))
    return (jnp.minimum(r, M - 1).astype(jnp.int32),
            jnp.minimum(c, N - 1).astype(jnp.int32))


def _frozen_unique_keys(key, rows, cols, N, nnz):
    k = jnp.sort(rows * N + cols)
    first = jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
    prio = jnp.where(first, jax.random.uniform(key, k.shape), 2.0)
    pick = jnp.argsort(prio)[:nnz]
    return k[pick], jnp.sum(first)


def _frozen_planted_values(key, keys, M, N, F, G, noise, rmin, rmax):
    @jax.jit
    def make(key, keys):
        ks = jax.random.split(key, 7)
        rows, cols = keys // N, keys % N
        group = jax.random.randint(ks[0], (N,), 0, G)
        s = 1.0 / math.sqrt(F)
        u = jax.random.normal(ks[1], (M, F)) * s
        v = jax.random.normal(ks[2], (N, F)) * s
        gdir = jax.random.normal(ks[3], (G, F)) * s
        v = v + 1.5 * gdir[group]
        bi = jax.random.normal(ks[4], (M,)) * 0.25
        bj = jax.random.normal(ks[5], (N,)) * 0.25
        raw = (jnp.sum(u[rows] * v[cols], -1) + bi[rows] + bj[cols]
               + jax.random.normal(ks[6], rows.shape) * noise)
        mid, amp = 0.5 * (rmin + rmax), 0.5 * (rmax - rmin)
        vals = jnp.clip(mid + amp * jnp.tanh(raw), rmin, rmax)
        return rows.astype(jnp.int32), cols.astype(jnp.int32), vals, group
    return make(key, keys)


def frozen_ratings(cfg, seed):
    M, N, nnz = cfg["M"], cfg["N"], cfg["nnz"]
    a, F = cfg["zipf_a"], cfg["rank"]
    key = gen.key_of(seed, 1)
    k_draw, k_pick, k_fac, k_split = jax.random.split(key, 4)
    take = int(nnz * 2.5) + 1024
    for attempt in range(8):
        rows, cols = _frozen_draw_pairs(jax.random.fold_in(k_draw, attempt),
                                        M, N, take, a)
        keys, distinct = jax.jit(_frozen_unique_keys, static_argnums=(3, 4))(
            k_pick, rows, cols, N, nnz)
        if int(distinct) >= nnz:
            break
        take = int(take * 1.6)
    else:
        raise RuntimeError(f"could not draw {nnz} distinct pairs")
    rows, cols, vals, group = _frozen_planted_values(
        k_fac, keys, M, N, F, cfg["groups"] or max(4, N // 50),
        cfg["noise"], cfg["rmin"], cfg["rmax"])
    perm = jax.random.permutation(k_split, nnz)
    n_test = int(nnz * cfg["test_frac"])
    te, tr = perm[:n_test], perm[n_test:]
    split = lambda idx: (rows[idx], cols[idx], vals[idx])
    return split(tr), split(te), group


def _flat(out):
    train, test, group = out
    return [np.asarray(a) for a in (*train, *test, group)]


def test_ratings_shape_range_and_split():
    train, test, group = gen.ratings(RATINGS, SEED)
    r = np.concatenate([np.asarray(train[0]), np.asarray(test[0])])
    c = np.concatenate([np.asarray(train[1]), np.asarray(test[1])])
    v = np.concatenate([np.asarray(train[2]), np.asarray(test[2])])
    assert r.size == 20000 and np.asarray(test[0]).size == 2000
    assert np.unique(r.astype(np.int64) * 300 + c).size == 20000
    assert r.min() >= 0 and r.max() < 500 and c.min() >= 0 and c.max() < 300
    assert v.min() >= 1.0 and v.max() <= 5.0 and 2.0 < v.mean() < 4.0
    assert np.asarray(group).max() < 6          # N // 50 groups
    again = gen.ratings(RATINGS, SEED)[0]
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(train, again))


def test_ratings_zipf_head_and_planted_groups():
    train, test, group = gen.ratings(RATINGS, SEED)
    c = np.concatenate([np.asarray(train[1]), np.asarray(test[1])])
    r = np.concatenate([np.asarray(train[0]), np.asarray(test[0])])
    v = np.concatenate([np.asarray(train[2]), np.asarray(test[2])])
    deg = np.bincount(c, minlength=300)
    # the 10% most rated items hold far more than 10% of the ratings
    assert np.sort(deg)[::-1][:30].sum() / deg.sum() > 0.25
    # items of one group are rated alike by the same users
    R = np.full((500, 300), np.nan)
    R[r, c] = v
    g = np.asarray(group)
    top = np.argsort(-deg)[:60]
    same, diff = [], []
    for a in range(len(top)):
        for b in range(a + 1, len(top)):
            i, j = top[a], top[b]
            both = ~np.isnan(R[:, i]) & ~np.isnan(R[:, j])
            if both.sum() < 20:
                continue
            rho = np.corrcoef(R[both, i], R[both, j])[0, 1]
            (same if g[i] == g[j] else diff).append(rho)
    assert len(same) > 5 and len(diff) > 5
    assert np.mean(same) > np.mean(diff) + 0.1


def test_catalog_groups_and_degree():
    cfg = dict(catalog_seed=5, N=500, F=8, items_per_group=50,
               users_per_group=32, deg=24, group_scale=1.6, noise=0.12,
               bias_std=0.15, mu=3.0)
    U, V, bh, rows, cols, vals = (np.asarray(a) for a in
                                  gen.catalog(cfg, SEED))
    assert U.shape == (320, 8) and V.shape == (500, 8) and bh.shape == (500,)
    assert rows.size == 500 * 24
    assert (np.bincount(cols, minlength=500) == 24).all()
    # every rater of an item is a distinct user of one group, and each
    # group of users rates 50 items
    grp = np.full(500, -1)
    grp[cols] = rows // 32
    assert (grp[cols] == rows // 32).all()
    assert (np.bincount(grp, minlength=10) == 50).all()
    assert np.unique(rows.astype(np.int64) * 500 + cols).size == rows.size
    assert vals.min() >= 1.0 and vals.max() <= 5.0
    # items of a group share a direction
    Vn = V / np.linalg.norm(V, axis=1, keepdims=True)
    a, b = np.flatnonzero(grp == 0)[:2]
    c = np.flatnonzero(grp == 1)[0]
    assert (Vn[a] @ Vn[b]) > 0.8 and abs(Vn[a] @ Vn[c]) < 0.8


def test_catalog_same_for_every_seed_under_other_ids():
    cfg = dict(catalog_seed=5, N=500, F=8, items_per_group=50,
               users_per_group=32, deg=24, group_scale=1.6, noise=0.12,
               bias_std=0.15, mu=3.0)
    U1, V1, bh1, *_ = (np.asarray(a) for a in gen.catalog(cfg, 1))
    U2, V2, bh2, *_ = (np.asarray(a) for a in gen.catalog(cfg, 2**31 + 5))
    assert np.array_equal(U1, U2)
    assert np.array_equal(np.sort(bh1), np.sort(bh2))
    assert not np.array_equal(bh1, bh2)
    order = np.argsort(bh1)[np.argsort(np.argsort(bh2))]
    assert np.array_equal(V1[order], V2)


def test_arrivals_same_gaps_in_another_order():
    d1, u1 = gen.arrivals(1000.0, 2.0, 5000, 1.0, 1)
    d2, u2 = gen.arrivals(1000.0, 2.0, 5000, 1.0, 2**31 + 5)
    assert d1.size == d2.size == 2000
    assert abs(d1[-1] - 2.0) < 1e-9 and abs(d2[-1] - 2.0) < 1e-9
    g1, g2 = np.diff(d1, prepend=0.0), np.diff(d2, prepend=0.0)
    assert np.allclose(np.sort(g1), np.sort(g2))
    assert not np.allclose(g1, g2)
    assert abs(g1.mean() - 1e-3) < 1e-5
    assert u1.min() >= 0 and u1.max() < 5000
    # Zipf(1.0) over 5000 users: the top user draws ~1/H(5000) ≈ 11%
    top = np.bincount(u1).max() / u1.size
    assert 0.07 < top < 0.16


@pytest.mark.parametrize("cfg,seed", [(RATINGS, SEED), (RATINGS, 7),
                                      (ML10M, SEED), (ML10M, 2**33 + 3)],
                         ids=["ratings", "ratings-small-seed", "ml10m-shape",
                              "ml10m-shape-wide-seed"])
def test_ratings_bit_for_bit_where_the_first_version_was_sound(cfg, seed):
    new, old = _flat(gen.ratings(cfg, seed)), _flat(frozen_ratings(cfg, seed))
    assert [a.dtype for a in new] == [a.dtype for a in old]
    for a, b in zip(new, old):
        assert np.array_equal(a, b)


def test_ratings_past_2_31_cells():
    cfg = dict(RATINGS, M=40000, N=624961, nnz=30000)
    assert cfg["M"] * cfg["N"] > 2**31
    train, test, _ = gen.ratings(cfg, SEED)
    r = np.concatenate([np.asarray(train[0]), np.asarray(test[0])])
    c = np.concatenate([np.asarray(train[1]), np.asarray(test[1])])
    assert r.dtype == c.dtype == np.int32
    assert r.size == 30000 and np.asarray(test[0]).size == 3000
    assert np.asarray(train[0]).size == 27000
    assert r.min() >= 0 and r.max() < 40000 and c.min() >= 0 and c.max() < 624961
    assert np.unique(r.astype(np.int64) * 624961 + c).size == 30000
    # the zipf tail of both sides is drawn, not folded onto low ids
    assert c.max() > 330280 and r.max() > 20000


def _zipf_bins(n, a):
    """Edges of log₂ bins of the ids (1-based ranks 2^k … 2^{k+1} − 1)."""
    edges = [0]
    while edges[-1] < n:
        edges.append(min(n, 2 * edges[-1] + 1))
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    p /= p.sum()
    return np.array(edges), np.add.reduceat(p, edges[:-1])


def test_zipf_draw_reaches_every_id_at_its_share():
    n, a, take = 624961, 1.2, 16_000_000
    ids = np.asarray(gen._zipf_draw(gen.key_of(SEED, 9), n, a, take))
    assert ids.min() >= 0 and ids.max() < n
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    p /= p.sum()
    seen = np.bincount(ids, minlength=n)
    # ids past 330,280 hold ≈ 0.9 % of the mass
    far = seen[330280:].sum() / take
    assert abs(far / p[330280:].sum() - 1) < 0.05
    # every log₂ bin of ranks within 5 % of the law (the last holds ≈
    # 35,000 draws)
    edges, want = _zipf_bins(n, a)
    got = np.add.reduceat(seen, edges[:-1]) / take
    assert np.all(np.abs(got / want - 1) < 0.05), got / want
    # each id of the tail is drawn: as many distinct ids past 330,280 as
    # the law gives 16M draws (≈ 113,000, each due 0.34–0.73 times), within
    # 3 %; a float32 CDF leaves most of them a step of zero width
    due = np.sum(-np.expm1(-take * p[330280:]))
    assert abs(np.count_nonzero(seen[330280:]) / due - 1) < 0.03
    # no excess on the last id: 0.34 draws are due
    assert seen[-1] <= 5
    # the draw's own odds of each id, from its tables, within 1 % of the
    # law for every id, the last one included
    outer, inner, B = gen._zipf_blocks(n, a)
    step = lambda c: np.diff(c.astype(np.float64), axis=-1, prepend=0.0)
    odds = (step(outer)[:, None] * step(inner.reshape(-1, B))).reshape(-1)[:n]
    assert abs(odds.sum() - 1) < 1e-6
    assert np.max(np.abs(odds / p - 1)) < 0.01


@pytest.mark.parametrize("n,a", [(1000990, 1.2), (624961, 1.0),
                                 (10677, 1.2), (27951, 1.2)])
def test_zipf_draw_follows_the_law_in_log_bins(n, a):
    take = 1_000_000
    ids = np.asarray(gen._zipf_draw(gen.key_of(SEED, 10), n, a, take))
    ids = np.minimum(ids, n - 1)
    edges, want = _zipf_bins(n, a)
    got = np.add.reduceat(np.bincount(ids, minlength=n), edges[:-1]) / take
    assert np.all(np.abs(got / want - 1) < 0.06), got / want
