"""Synthetic sparse-interaction data with MovieLens/Netflix-like statistics.

The paper evaluates on Netflix / MovieLens / Yahoo!Music, none of which are
redistributable in this container (DESIGN.md §8.4).  This generator matches
the *structural* statistics that drive the algorithms: zipf-tailed item/user
popularity (which drives LSH bucket skew and load balance), a planted
low-rank + neighbourhood signal (so RMSE orderings between methods are
meaningful), bounded rating ranges, and the paper's train/test split shape.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    M: int
    N: int
    nnz: int
    rmin: float = 1.0
    rmax: float = 5.0
    rank: int = 8
    zipf_a: float = 1.2
    noise: float = 0.35
    neigh_groups: int = 0  # planted item-cluster count; 0 = N // 50


# Reduced-scale analogues of the paper's Table 2 (full sizes are reachable by
# passing scale=1.0; tests/benches default to small fractions to stay CPU-fast).
MOVIELENS_LIKE = DatasetSpec("movielens-like", 69_878, 10_677, 9_900_054)
NETFLIX_LIKE = DatasetSpec("netflix-like", 480_189, 17_770, 99_072_112)
YAHOO_LIKE = DatasetSpec("yahoo-like", 586_250, 12_658, 91_970_212, rmax=100.0)


def scaled(spec: DatasetSpec, scale: float) -> DatasetSpec:
    return dataclasses.replace(
        spec,
        M=max(64, int(spec.M * scale)),
        N=max(32, int(spec.N * scale)),
        nnz=int(spec.nnz * scale * scale),
    )


def generate(spec: DatasetSpec, seed: int = 0):
    """Returns COO triples (rows, cols, vals) with a planted signal.

    Ground truth: r = clip(mu + b_i + b_j + u_i·v_j + group(j) bump, rmin, rmax)
    where items within a group share a latent direction — this is the
    neighbourhood structure that Top-K methods are supposed to exploit, so
    GSM/simLSH beat Rand-K on RMSE exactly as in the paper's Fig. 7.
    """
    rng = np.random.default_rng(seed)
    M, N, nnz = spec.M, spec.N, spec.nnz

    # zipf popularity for both sides (sorted → id 0 most popular)
    pu = 1.0 / np.arange(1, M + 1) ** spec.zipf_a
    pi = 1.0 / np.arange(1, N + 1) ** spec.zipf_a
    pu /= pu.sum()
    pi /= pi.sum()

    # oversample until nnz unique pairs (zipf heads collide a lot).  The
    # distinct keys drawn so far stay in one sorted array, so each round
    # costs its own draw plus one linear merge, not a sort of everything
    # drawn (the tail rounds are many and small at full size)
    rows_l, cols_l = [], []
    distinct = np.zeros((0,), np.int64)
    want = nnz
    while distinct.size < want:
        take = int((want - distinct.size) * 2.0) + 1024
        r = rng.choice(M, size=take, p=pu).astype(np.int32)
        c = rng.choice(N, size=take, p=pi).astype(np.int32)
        rows_l.append(r)
        cols_l.append(c)
        new = np.unique(r.astype(np.int64) * N + c)
        at = np.searchsorted(distinct, new)
        known = at < distinct.size
        known[known] = distinct[at[known]] == new[known]
        distinct = np.insert(distinct, at[~known], new[~known])
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    key = rows.astype(np.int64) * N + cols
    _, uniq = np.unique(key, return_index=True)
    rng.shuffle(uniq)
    uniq = uniq[: nnz]
    rows, cols = rows[uniq], cols[uniq]

    G = spec.neigh_groups or max(4, N // 50)
    group = rng.integers(0, G, size=N)

    F = spec.rank
    u = rng.normal(0, 1.0 / np.sqrt(F), (M, F))
    v = rng.normal(0, 1.0 / np.sqrt(F), (N, F))
    gdir = rng.normal(0, 1.0 / np.sqrt(F), (G, F))
    v = v + 1.5 * gdir[group]  # planted neighbourhood signal

    mid = 0.5 * (spec.rmin + spec.rmax)
    amp = 0.5 * (spec.rmax - spec.rmin)
    bi = rng.normal(0, 0.25, M)
    bj = rng.normal(0, 0.25, N)
    raw = (u[rows] * v[cols]).sum(-1) + bi[rows] + bj[cols]
    raw = raw + rng.normal(0, spec.noise, raw.shape)
    vals = np.clip(mid + amp * np.tanh(raw), spec.rmin, spec.rmax).astype(np.float32)
    return rows, cols, vals, group


def add_noise(rng: np.random.Generator, vals, rate: float, rmin: float, rmax: float):
    """Paper Table 8 robustness protocol: corrupt `rate` of ratings uniformly."""
    vals = vals.copy()
    k = int(len(vals) * rate)
    idx = rng.choice(len(vals), size=k, replace=False)
    vals[idx] = rng.uniform(rmin, rmax, size=k).astype(np.float32)
    return vals
