"""Plain reference of CULSH-MF training (paper Eq. 1 and Eq. 5).

Straightforward `jax.numpy`, independent of the program: the training
ratings live in one dense ``[M, N]`` matrix (0 = unrated), so a neighbour
rating is a plain index, and the K neighbours of an item are the exact
top-K cosine similarities of the Ψ(r) = r² weighted item columns, the
quantity simLSH estimates with its signatures.  SGD visits the training
ratings in a random order per epoch, in blocks: the ratings of a block
that touch a row and an item no other does first get their whole Eq. (5)
step, the rest of the block takes averaged steps, as the program's
leftover batches do; the Eq. (7) decay applies per epoch.  Training
runs in ``dtype`` (float32 for the reference; the control passes
bfloat16); the held-out RMSE is always taken in float32, and matrix
products run at HIGHEST precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dense(rows, cols, vals, M: int, N: int):
    return jnp.zeros((M, N), jnp.float32).at[rows, cols].set(
        vals.astype(jnp.float32))


@partial(jax.jit, static_argnames=("K", "block"))
def neighbours(R, *, K: int, block: int = 1024):
    """Exact top-K of the cosine similarity of the r²-weighted columns
    (self excluded) → [N, K] int32."""
    X = R * R
    norm = jnp.sqrt(jnp.maximum(jnp.sum(X * X, 0), 1e-12))
    Xn = X / norm[None, :]
    N = R.shape[1]
    nblk = -(-N // block)
    Xp = jnp.pad(Xn, ((0, 0), (0, nblk * block - N)))

    def tile(start):
        sl = jax.lax.dynamic_slice_in_dim(Xp, start, block, 1)
        S = jnp.dot(sl.T, Xn, precision=HIGHEST)           # [block, N]
        own = start + jnp.arange(block)
        S = jnp.where(jnp.arange(N)[None, :] == own[:, None], -jnp.inf, S)
        return jax.lax.top_k(S, K)[1].astype(jnp.int32)

    return jax.lax.map(tile, jnp.arange(nblk) * block).reshape(-1, K)[:N]


def predict(p, R, JK, i, j):
    """Eq. (1) for the pairs (i, j) → (prediction, parts for the update)."""
    nb = JK[j]                                               # [B, K]
    rnb = R[i[:, None], nb]
    expl = (rnb > 0).astype(rnb.dtype)
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
def rmse(p, R, JK, rows, cols, vals, *, batch: int = 8192):
    """Held-out RMSE of Eq. (1), in float32 whatever the parameters' dtype."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    R = R.astype(jnp.float32)
    n = rows.shape[0]
    nb = -(-n // batch)
    pad = lambda a: jnp.pad(a, (0, nb * batch - n))
    valid = pad(jnp.ones((n,), jnp.float32))
    rows, cols, vals = pad(rows), pad(cols), pad(vals.astype(jnp.float32))

    def body(acc, s):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, s, batch)
        pred = predict(p, R, JK, sl(rows), sl(cols))[0]
        return acc + jnp.sum((sl(vals) - pred) ** 2 * sl(valid)), None

    sse, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          jnp.arange(nb) * batch)
    return jnp.sqrt(sse / n)


def _add_rows(x, idx, delta):
    """``x`` with ``delta[b]`` added to row ``idx[b]`` for every b, summed
    where rows repeat: a one-hot product, which the TPU runs far faster
    than a scatter of a few hundred rows."""
    oh = jax.nn.one_hot(idx, x.shape[0], dtype=delta.dtype)
    if delta.ndim == 1:
        return x + jnp.dot(delta, oh, precision=HIGHEST,
                           preferred_element_type=x.dtype)
    return x + jnp.dot(oh.T, delta, precision=HIGHEST,
                       preferred_element_type=x.dtype)


def _apply(p, R, JK, i, j, r, on, si, sj, g, d):
    """Eq. (5) for the ratings where ``on``, their row and item steps
    scaled by ``si`` and ``sj``."""
    pred, (resid, expl, impl, sR, sN) = predict(p, R, JK, i, j)
    e = (r - pred) * on
    ui, vj, wj, cj = p["U"][i], p["V"][j], p["W"][j], p["C"][j]
    q = dict(p)
    q["b"] = _add_rows(p["b"], i,
                       g["a_b"] * d * (e - g["l_b"] * p["b"][i]) * si)
    q["bh"] = _add_rows(p["bh"], j,
                        g["a_bh"] * d * (e - g["l_bh"] * p["bh"][j]) * sj)
    q["U"] = _add_rows(p["U"], i, g["a_u"] * d
                       * (e[:, None] * vj - g["l_u"] * ui) * si[:, None])
    q["V"] = _add_rows(p["V"], j, g["a_v"] * d
                       * (e[:, None] * ui - g["l_v"] * vj) * sj[:, None])
    q["W"] = _add_rows(p["W"], j, g["a_w"] * d
                       * (sR[:, None] * e[:, None] * resid - g["l_w"] * wj)
                       * expl * sj[:, None])
    q["C"] = _add_rows(p["C"], j, g["a_c"] * d
                       * (sN[:, None] * e[:, None] - g["l_c"] * cj) * impl
                       * sj[:, None])
    return q


@partial(jax.jit, static_argnames=("batch", "fault"))
def epoch(p, R, JK, rows, cols, vals, key, t, hp, *, batch: int,
          fault: str = ""):
    """One epoch of Eq. (5) SGD over the training ratings in a random
    order, in blocks of ``batch``.  A block is applied in two parts: first
    every rating that comes first in the block for both its row and its
    item, each with its whole step (no two touch the same row or item);
    then the block's other ratings together, where a row or item touched
    several times takes the mean of their updates.  ``fault`` plants a
    known defect for the benchmark's own tests: "frozen" returns the state
    unchanged, "half_batch" leaves every other rating of a block out."""
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
        i, j, r = rows[idx], cols[idx], vals[idx].astype(dt)
        ok = jax.lax.dynamic_slice_in_dim(valid, s, batch)
        same_i, same_j = i[:, None] == i[None, :], j[:, None] == j[None, :]
        first = ok & ~jnp.any((same_i | same_j) & earlier & ok[None, :], 1)
        one = first.astype(dt)
        p = _apply(p, R, JK, i, j, r, one, one, one, g, d)
        rest = (ok & ~first).astype(dt)
        ci = jnp.maximum(jnp.sum(same_i * rest[None, :], 1), 1)
        cj = jnp.maximum(jnp.sum(same_j * rest[None, :], 1), 1)
        return _apply(p, R, JK, i, j, r, rest, rest / ci, rest / cj, g,
                      d), None

    if fault == "frozen":
        return p
    return jax.lax.scan(block, p, jnp.arange(nb) * batch)[0]


def train(p0, train_coo, test_coo, M: int, N: int, K: int, epochs: int,
          hp: dict, key, *, batch: int, dtype=jnp.float32, fault: str = "",
          on_epoch=None):
    """``epochs`` epochs from ``p0``, trained in ``dtype`` and evaluated in
    float32 → (final params, [held-out RMSE after each epoch], the
    neighbour lists).  ``on_epoch(t, rmse)`` is called after each."""
    R = dense(*train_coo, M, N)
    JK = neighbours(R, K=K)
    Rt = R.astype(dtype)
    p = {k: jnp.asarray(v, dtype) for k, v in p0.items()}
    tr = (train_coo[0], train_coo[1], train_coo[2].astype(dtype))
    curve = []
    for t in range(epochs):
        p = epoch(p, Rt, JK, *tr, jax.random.fold_in(key, t),
                  jnp.asarray(t, jnp.float32), hp, batch=batch, fault=fault)
        curve.append(float(rmse(p, R, JK, *test_coo)))
        if on_epoch:
            on_epoch(t, curve[-1])
    return p, curve, JK
