"""Plain sparse reference of CULSH-MF training (paper Eq. 1 and Eq. 5).

The mathematics and order of ``ml10m-culsh.ref.py``: Eq. (1) for the
prediction; Eq. (5) SGD over the training ratings in a random order per
epoch, in blocks, where the ratings of a block that touch a row and an
item no other does first get their whole step and the rest of the block
takes averaged steps; the Eq. (7) decay per epoch; training in ``dtype``
(float32, or bfloat16 for the control), the held-out RMSE in float32;
the ``half_batch`` and ``frozen`` faults.
It departs from that reference in three ways, because a 624,961-item
catalog has no dense [M, N] matrix (39 GB at one chip's share) and no
exact top-K of its item columns inside a run:

(a) the training ratings stay sparse, sorted by (row, col) with a
    directory of each row — what ``dense`` returns — and each rating's K
    neighbour ratings and explicit flags are found once, by one join (a
    search in the row's sorted columns), for the training and the
    held-out ratings alike; a
    neighbour is explicit where the pair is rated, whatever its value;
(b) updates are scatter-adds: a one-hot product over 624,961 items would
    take 1.28 GB a block;
(c) ``train`` takes the neighbour lists ``JK`` as an argument (the
    program's, so both train the same model); ``neighbour_gap`` judges
    those lists against the exact cosine top-K of a sample of items.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16            # ratings a join or accumulation step handles
SPAN = 256                 # columns a row directory entry covers


def dense(rows, cols, vals, M: int, N: int):
    """The training ratings sorted by (row, col), with a directory of
    each row: dict(cols, vals, at [M, ⌈N / SPAN⌉ + 1]), where ``at[i, b]``
    is the first position of row i whose column is ≥ b · SPAN (the row's
    end past its last column)."""
    return _dense(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                  jnp.asarray(vals, jnp.float32), M=M, N=N)


@partial(jax.jit, static_argnames=("M", "N"))
def _dense(rows, cols, vals, *, M: int, N: int):
    r, c, v = jax.lax.sort((rows, cols, vals), num_keys=2)
    end = jnp.searchsorted(r, jnp.arange(1, M + 1, dtype=jnp.int32))
    nb = -(-N // SPAN) + 1
    at = jnp.full((M, nb), r.shape[0], jnp.int32)
    at = at.at[r, c // SPAN].min(jnp.arange(r.shape[0], dtype=jnp.int32))
    at = at.at[:, -1].set(end.astype(jnp.int32))
    return dict(cols=c, vals=v, at=jax.lax.cummin(at, axis=1, reverse=True))


def _chunked(fn, n: int, *arrays):
    """``fn`` over ``arrays`` in chunks of `CHUNK` along axis 0 →
    the results concatenated and cut to ``n``."""
    nc = -(-n // CHUNK)
    pad = lambda a: jnp.pad(a, [(0, nc * CHUNK - n)] + [(0, 0)] * (a.ndim - 1))
    xs = tuple(pad(a).reshape((nc, CHUNK) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda x: fn(*x), xs)
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:n], out)


@jax.jit
def join(R, JK, rows, cols):
    """The K neighbour ratings of each rating (i, j): r_{i, J^K[j][k]}
    where row i rates that item, else 0, and the explicit flags →
    (rnb [n, K], expl [n, K]) float32.  Each is a search in row i's
    sorted columns: the directory gives the stretch of the row that
    holds the neighbour's SPAN-wide band of columns, and a binary search
    finds it there."""
    c_s, v_s, at = R["cols"], R["vals"], R["at"]
    last = c_s.shape[0] - 1

    def one(i, j):
        nb = JK[j]                                          # [B, K]
        band = nb // SPAN
        lo, hi = at[i[:, None], band], at[i[:, None], band + 1]
        end = hi
        for _ in range(SPAN.bit_length()):      # first column ≥ nb
            mid = (lo + hi) // 2
            left = (lo < hi) & (c_s[jnp.minimum(mid, last)] < nb)
            lo, hi = jnp.where(left, mid + 1, lo), jnp.where(left, hi, mid)
        pos = jnp.minimum(lo, last)
        hit = (lo < end) & (c_s[pos] == nb)
        return jnp.where(hit, v_s[pos], 0.0), hit.astype(jnp.float32)

    return _chunked(one, rows.shape[0], rows, cols)


def predict(p, JK, i, j, rnb, expl):
    """Eq. (1) for the pairs (i, j) with their neighbour ratings and
    flags → (prediction, parts for the update)."""
    nb = JK[j]                                               # [B, K]
    impl = 1 - expl
    base_nb = p["mu"] + p["b"][i][:, None] + p["bh"][nb]
    resid = (rnb - base_nb) * expl
    nR, nN = jnp.sum(expl, 1), jnp.sum(impl, 1)
    sR = jnp.where(nR > 0, 1 / jnp.sqrt(jnp.maximum(nR, 1)), 0)
    sN = jnp.where(nN > 0, 1 / jnp.sqrt(jnp.maximum(nN, 1)), 0)
    pred = (p["mu"] + p["b"][i] + p["bh"][j]
            + sR * jnp.sum(resid * p["W"][j], 1)
            + sN * jnp.sum(impl * p["C"][j], 1)
            + jnp.sum(p["U"][i] * p["V"][j], 1))
    return pred, (resid, expl, impl, sR, sN)


@partial(jax.jit, static_argnames=("batch",))
def _sse(p, JK, rows, cols, vals, rnb, expl, *, batch: int):
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    n = rows.shape[0]
    nb = -(-n // batch)
    pad = lambda a: jnp.pad(a, [(0, nb * batch - n)] + [(0, 0)] * (a.ndim - 1))
    valid = pad(jnp.ones((n,), jnp.float32))
    xs = (pad(rows), pad(cols), pad(vals.astype(jnp.float32)), pad(rnb),
          pad(expl), valid)

    def body(acc, s):
        i, j, r, rn, ex, ok = (jax.lax.dynamic_slice_in_dim(a, s, batch)
                               for a in xs)
        pred = predict(p, JK, i, j, rn, ex)[0]
        return acc + jnp.sum((r - pred) ** 2 * ok), None

    sse, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          jnp.arange(nb) * batch)
    return jnp.sqrt(sse / n)


def rmse(p, R, JK, rows, cols, vals, *, batch: int = 8192, joined=None):
    """Held-out RMSE of Eq. (1), in float32 whatever the parameters'
    dtype; ``joined`` passes the held-out join when it is already made."""
    rnb, expl = joined if joined is not None else join(R, JK, rows, cols)
    return _sse(p, JK, rows, cols, vals, rnb, expl, batch=batch)


def _apply(p, JK, i, j, r, rnb, expl, on, si, sj, g, d):
    """Eq. (5) for the ratings where ``on``, their row and item steps
    scaled by ``si`` and ``sj``; each parameter takes its deltas by a
    scatter-add, summed where a row repeats."""
    pred, (resid, expl, impl, sR, sN) = predict(p, JK, i, j, rnb, expl)
    e = (r - pred) * on
    ui, vj, wj, cj = p["U"][i], p["V"][j], p["W"][j], p["C"][j]
    q = dict(p)
    q["b"] = p["b"].at[i].add(g["a_b"] * d * (e - g["l_b"] * p["b"][i]) * si)
    q["bh"] = p["bh"].at[j].add(
        g["a_bh"] * d * (e - g["l_bh"] * p["bh"][j]) * sj)
    q["U"] = p["U"].at[i].add(g["a_u"] * d * (e[:, None] * vj - g["l_u"] * ui)
                              * si[:, None])
    q["V"] = p["V"].at[j].add(g["a_v"] * d * (e[:, None] * ui - g["l_v"] * vj)
                              * sj[:, None])
    q["W"] = p["W"].at[j].add(g["a_w"] * d * (sR[:, None] * e[:, None] * resid
                                              - g["l_w"] * wj)
                              * expl * sj[:, None])
    q["C"] = p["C"].at[j].add(g["a_c"] * d * (sN[:, None] * e[:, None]
                                              - g["l_c"] * cj)
                              * impl * sj[:, None])
    return q


@partial(jax.jit, static_argnames=("batch", "fault"))
def epoch(p, JK, rows, cols, vals, rnb, expl, key, t, hp, *, batch: int,
          fault: str = ""):
    """One epoch of Eq. (5) SGD over the training ratings in a random
    order, in blocks of ``batch``, as ``ml10m-culsh.ref.py``'s: first
    every rating of a block that comes first for both its row and its
    item, each with its whole step; then the block's other ratings
    together, a row or item touched several times taking the mean of
    their updates.  "frozen" returns the state unchanged, "half_batch"
    leaves every other rating of a block out."""
    dt = p["U"].dtype
    n = rows.shape[0]
    nb = -(-n // batch)
    perm = jax.random.permutation(key, n)
    perm = jnp.pad(perm, (0, nb * batch - n))
    valid = jnp.arange(nb * batch) < n
    if fault == "half_batch":
        valid &= jnp.arange(nb * batch) % 2 == 0
    d = (1.0 / (1.0 + hp["beta"] * t ** 1.5)).astype(dt)
    g = {k: jnp.asarray(v, dt) for k, v in hp.items()}
    pos = jnp.arange(batch)
    earlier = pos[None, :] < pos[:, None]

    def block(p, s):
        idx = jax.lax.dynamic_slice_in_dim(perm, s, batch)
        i, j, r = rows[idx], cols[idx], vals[idx]
        rn, ex = rnb[idx], expl[idx]
        ok = jax.lax.dynamic_slice_in_dim(valid, s, batch)
        same_i, same_j = i[:, None] == i[None, :], j[:, None] == j[None, :]
        first = ok & ~jnp.any((same_i | same_j) & earlier & ok[None, :], 1)
        one = first.astype(dt)
        p = _apply(p, JK, i, j, r, rn, ex, one, one, one, g, d)
        rest = (ok & ~first).astype(dt)
        ci = jnp.maximum(jnp.sum(same_i * rest[None, :], 1), 1)
        cj = jnp.maximum(jnp.sum(same_j * rest[None, :], 1), 1)
        return _apply(p, JK, i, j, r, rn, ex, rest, rest / ci, rest / cj, g,
                      d), None

    if fault == "frozen":
        return p
    return jax.lax.scan(block, p, jnp.arange(nb) * batch)[0]


def train(p0, train_coo, test_coo, M: int, N: int, K: int, epochs: int,
          hp: dict, key, *, batch: int, JK, dtype=jnp.float32,
          fault: str = "", on_epoch=None):
    """``epochs`` epochs from ``p0`` on the neighbour lists ``JK``
    [N, K], trained in ``dtype`` and evaluated in float32 → (final
    params, [held-out RMSE after each epoch], ``JK``).  ``on_epoch(t,
    rmse)`` is called after each."""
    JK = jnp.asarray(JK, jnp.int32)
    R = dense(*train_coo, M, N)
    rnb, expl = join(R, JK, train_coo[0], train_coo[1])
    held = join(R, JK, test_coo[0], test_coo[1])
    p = {k: jnp.asarray(v, dtype) for k, v in p0.items()}
    tr = (train_coo[0], train_coo[1], train_coo[2].astype(dtype),
          rnb.astype(dtype), expl.astype(dtype))
    curve = []
    for t in range(epochs):
        p = epoch(p, JK, *tr, jax.random.fold_in(key, t),
                  jnp.asarray(t, jnp.float32), hp, batch=batch, fault=fault)
        curve.append(float(rmse(p, R, JK, *test_coo, joined=held)))
        if on_epoch:
            on_epoch(t, curve[-1])
    return p, curve, JK


@partial(jax.jit, static_argnames=("M", "N"))
def _sample_cosines(rows, cols, vals, items, *, M: int, N: int):
    """Exact cosines of the Ψ = r² item columns of ``items`` [S] against
    every item → [N, S], in one pass over the ratings."""
    S = items.shape[0]
    psi = vals.astype(jnp.float32) ** 2
    norm = jnp.sqrt(jnp.zeros((N,), jnp.float32).at[cols].add(psi * psi))
    slot = jnp.full((N,), S, jnp.int32).at[items].set(
        jnp.arange(S, dtype=jnp.int32))
    # the sampled columns, dense over the users: D[i, s] = Ψ(r_{i, items[s]})
    D = jnp.zeros((M, S + 1), jnp.float32).at[rows, slot[cols]].set(psi)[:, :S]
    acc = jnp.zeros((N, S), jnp.float32)

    def add(acc, x):
        i, j, w = x
        return acc.at[j].add(w[:, None] * D[i]), None

    n = rows.shape[0]
    nc = -(-n // CHUNK)
    pad = lambda a: jnp.pad(a, (0, nc * CHUNK - n)).reshape(nc, CHUNK)
    acc, _ = jax.lax.scan(add, acc, (pad(rows), pad(cols), pad(psi)))
    return acc / jnp.maximum(norm[:, None] * norm[items][None, :], 1e-30)


def neighbour_gap(train_coo, JK, N: int, seed: int, n_items: int = 256):
    """How far the lists ``JK`` fall short of the exact neighbours: for
    ``n_items`` rated items drawn from ``seed``, 1 − (mean exact cosine
    of the Ψ = r² columns of each item and its K listed neighbours) /
    (mean of each item's exact top-K cosines, itself left out)."""
    rows, cols, vals = (jnp.asarray(a) for a in train_coo)
    JK = jnp.asarray(JK, jnp.int32)
    M = int(jnp.max(rows)) + 1
    rated = np.flatnonzero(np.bincount(np.asarray(cols), minlength=N))
    items = np.random.default_rng(int(seed)).choice(
        rated, min(n_items, rated.size), replace=False)
    items = jnp.asarray(items, jnp.int32)
    cos = _sample_cosines(rows, cols, vals, items, M=M, N=N).T   # [S, N]
    given = jnp.take_along_axis(cos, JK[items], 1)
    own = jnp.arange(N)[None, :] == items[:, None]
    best = jax.lax.top_k(jnp.where(own, -jnp.inf, cos), JK.shape[1])[0]
    return 1.0 - float(jnp.sum(given)) / float(jnp.sum(best))
