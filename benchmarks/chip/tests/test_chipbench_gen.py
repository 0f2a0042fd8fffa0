"""Statistics the benchmark's generators keep: shape, nnz, rating range,
zipf head share and planted-group structure."""
import sys
from pathlib import Path

import numpy as np

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import gen  # noqa: E402

RATINGS = dict(M=500, N=300, nnz=20000, test_frac=0.1, zipf_a=1.2, rank=8,
               noise=0.35, groups=0, rmin=1.0, rmax=5.0)
SEED = 2**31 + 12345


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
