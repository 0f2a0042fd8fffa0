"""Seeded input generators of the chip benchmark.

Everything a cell feeds the system is made here from ``--seed``, so the
program under test receives only arrays.  Two of these are copies, moved
onto the device, of generators the repository keeps elsewhere:

* `ratings` — the MovieLens-shaped ratings of `repro.data.synthetic.
  generate`: zipf popularity on both sides, unique (user, item) pairs, a
  planted rank-8 signal with item groups that share a latent direction,
  ratings squashed into [rmin, rmax].  It holds for any M and N up to a
  few million each, M·N past 2³¹ included: pairs are told apart by a sort
  on (row, col), never by a flat int32 key, and every id is drawn at its
  Zipf share (`_zipf_draw`);
* `catalog` — the planted-group catalog of `benchmarks/bench_serve.
  make_catalog`: items in groups of 50, users in groups of 32, each item
  rated by `deg` distinct users of its own group; one catalog for every
  run, its item ids permuted by the seed.

`arrivals` is the open-loop schedule of the serving mixes: Poisson-shaped
gaps and Zipf-distributed users.  Every seed gets the same multiset of
gaps, in its own order, so runs differ in who arrives when and not in how
much work the window holds.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's pass 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF),
                              salt)


# The float32 inverse CDF gives an id its share only while that share spans
# a few steps of the uniform draw's 2⁻²³ grid and of the CDF's ulps near 1;
# below 2⁻²¹ of the mass the tail's steps coarsen, then vanish.
_FINE = 2.0 ** -21


def _zipf_cdf(n: int, a: float) -> jax.Array:
    p = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32) ** a
    return jnp.cumsum(p) / jnp.sum(p)


def _zipf_p(n: int, a: float) -> np.ndarray:
    return np.arange(1, n + 1, dtype=np.float64) ** -a


def _zipf_blocks(n: int, a: float):
    """Two-level inverse CDF of Zipf(a) over n ids → (outer [nb], inner
    [nb·B], B): the CDF over blocks of B ids (a power of two ≥ √n), then
    each block's own CDF, both summed in float64 and rounded to float32
    (padding past id n reads 1.0).  Every id's step at its level is at
    least `_FINE`."""
    B = 1 << math.ceil(math.log2(math.sqrt(n)))
    nb = -(-n // B)
    p = np.zeros(nb * B)
    p[:n] = _zipf_p(n, a)
    p = p.reshape(nb, B)
    mass = p.sum(1)
    outer = np.cumsum(mass) / mass.sum()
    inner = np.cumsum(p, 1) / mass[:, None]
    outer[-1] = 1.0
    inner[:, -1] = 1.0
    inner.reshape(-1)[n:] = 1.0
    outer, inner = outer.astype(np.float32), inner.astype(np.float32)
    steps = np.diff(inner, axis=1, prepend=0.0).reshape(-1)[:n]
    if min(np.diff(outer, prepend=0.0).min(), steps.min()) < _FINE:
        raise ValueError(f"Zipf({a}) over {n} ids is past what two float32 "
                         "levels resolve")
    return outer, inner.reshape(-1), B


def _zipf_draw(key, n: int, a: float, take: int) -> jax.Array:
    """``take`` ids in [0, n) with P(j) ∝ (j + 1)^−a.  Where every id's
    share is at least `_FINE`, one float32 inverse CDF; past that, the
    two-level one of `_zipf_blocks`, which reaches every id and leaves the
    last no excess.  The choice rests on (n, a) alone."""
    p = _zipf_p(n, a)
    if p[-1] / p.sum() >= _FINE:
        return jnp.searchsorted(_zipf_cdf(n, a),
                                jax.random.uniform(key, (take,)))
    outer, inner, B = _zipf_blocks(n, a)
    return _two_level(key, jnp.asarray(outer), jnp.asarray(inner), B, take)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _two_level(key, outer, inner, B: int, take: int):
    u = jax.random.uniform(key, (2, take))
    base = jnp.minimum(jnp.searchsorted(outer, u[0]),
                       outer.shape[0] - 1).astype(jnp.int32) * B
    # lower bound of u[1] in the block's CDF, whose last entry is 1.0 > u
    pos = jnp.zeros_like(base)
    step = B // 2
    while step:
        pos = jnp.where(inner[base + pos + step - 1] < u[1], pos + step, pos)
        step //= 2
    return base + pos


def _draw_pairs(key, M: int, N: int, take: int, a: float):
    ku, ki = jax.random.split(key)
    r = _zipf_draw(ku, M, a, take)
    c = _zipf_draw(ki, N, a, take)
    return (jnp.minimum(r, M - 1).astype(jnp.int32),
            jnp.minimum(c, N - 1).astype(jnp.int32))


def _unique_pairs(key, rows, cols, nnz: int):
    """``nnz`` distinct (row, col) pairs drawn uniformly from the distinct
    pairs in ``rows``/``cols``, and how many there were.  The pairs are
    sorted by (row, col), which is the order of ``row · N + col`` without
    forming it, so M·N may pass 2³¹."""
    r, c = jax.lax.sort((rows, cols), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (r[1:] != r[:-1]) | (c[1:] != c[:-1])])
    prio = jnp.where(first, jax.random.uniform(key, r.shape), 2.0)
    pick = jnp.argsort(prio)[:nnz]
    return r[pick], c[pick], jnp.sum(first)


def ratings(cfg: dict, seed: int):
    """(train, test, group): COO triples on the device, ``test_frac`` held
    out, and the planted item group of each item.  ``cfg`` is the
    configuration's ``data`` block."""
    M, N, nnz = cfg["M"], cfg["N"], cfg["nnz"]
    a, F = cfg["zipf_a"], cfg["rank"]
    key = key_of(seed, 1)
    k_draw, k_pick, k_fac, k_split = jax.random.split(key, 4)
    # zipf heads collide, so draw with room and grow the draw until there
    # are enough distinct pairs (the same rounds for a given seed)
    take = int(nnz * 2.5) + 1024
    for attempt in range(8):
        rows, cols = _draw_pairs(jax.random.fold_in(k_draw, attempt), M, N,
                                 take, a)
        rows, cols, distinct = jax.jit(_unique_pairs, static_argnums=3)(
            k_pick, rows, cols, nnz)
        if int(distinct) >= nnz:
            break
        take = int(take * 1.6)
    else:
        raise RuntimeError(f"could not draw {nnz} distinct pairs")
    rows, cols, vals, group = _planted_values(
        k_fac, rows, cols, M, N, F, cfg["groups"] or max(4, N // 50),
        cfg["noise"], cfg["rmin"], cfg["rmax"])
    perm = jax.random.permutation(k_split, nnz)
    n_test = int(nnz * cfg["test_frac"])
    te, tr = perm[:n_test], perm[n_test:]
    split = lambda idx: (rows[idx], cols[idx], vals[idx])
    train, test = split(tr), split(te)
    jax.block_until_ready((train, test))
    return train, test, group


def _planted_values(key, rows, cols, M, N, F, G, noise, rmin, rmax):
    @jax.jit
    def make(key, rows, cols):
        ks = jax.random.split(key, 7)
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
        return rows, cols, vals, group
    return make(key, rows, cols)


def catalog(cfg: dict, seed: int):
    """Planted catalog on the device → (U, V, bh, rows, cols, vals) with
    ``M = (N // items_per_group) · users_per_group`` users.  Its content
    comes from the configuration's ``catalog_seed``, so every run serves
    the same catalog and its recall moves with the program alone; the
    run's ``seed`` gives the items their ids."""
    N, F = cfg["N"], cfg["F"]
    ipg, upg, deg = cfg["items_per_group"], cfg["users_per_group"], cfg["deg"]
    G = max(1, N // ipg)
    M = G * upg

    @jax.jit
    def make(key, k_ids):
        ks = jax.random.split(key, 5)
        g_item = (jnp.arange(N) // ipg) % G
        g_user = jnp.arange(M) // upg
        gdir = jax.random.normal(ks[0], (G, F))
        gdir = gdir / jnp.linalg.norm(gdir, axis=1, keepdims=True)
        gdir = gdir * cfg["group_scale"]
        U = gdir[g_user] + cfg["noise"] * jax.random.normal(ks[1], (M, F))
        V = gdir[g_item] + cfg["noise"] * jax.random.normal(ks[2], (N, F))
        bh = cfg["bias_std"] * jax.random.normal(ks[3], (N,))
        pick = jnp.argsort(jax.random.uniform(ks[4], (N, upg)), axis=1)
        rows = (pick[:, :deg] + g_item[:, None] * upg).reshape(-1)
        cols = jnp.repeat(jnp.arange(N, dtype=jnp.int32), deg)
        dots = jnp.sum(U[rows] * V[cols], -1)
        vals = jnp.clip(3.0 + 1.5 * dots, 1.0, 5.0)
        # item k is served under the id new_id[k]
        order = jax.random.permutation(k_ids, N)
        new_id = jnp.argsort(order).astype(jnp.int32)
        return (U, V[order], bh[order], rows.astype(jnp.int32),
                new_id[cols], vals)

    out = make(key_of(cfg["catalog_seed"], 2), key_of(seed, 2))
    jax.block_until_ready(out)
    return out


def arrivals(rate: float, seconds: float, M: int, zipf_a: float, seed: int):
    """Open-loop schedule → (due [n] seconds from the window start, users
    [n] int32).  ``n = rate · seconds``; the gaps are the exponential
    quantiles at n evenly spaced levels (so their mean is 1/rate in every
    run), shuffled by the seed; users follow a Zipf law over a seeded
    permutation of the user ids."""
    rng = np.random.default_rng(int(seed))
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    due *= seconds / due[-1]
    p = 1.0 / np.arange(1, M + 1) ** zipf_a
    ranks = np.searchsorted(np.cumsum(p) / p.sum(), rng.random(n))
    users = rng.permutation(M)[np.minimum(ranks, M - 1)]
    return due, users.astype(np.int32)
