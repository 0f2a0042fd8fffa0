"""Plain reference of top-N serving (the serving part of paper Eq. 1).

The score of item j for user u is ``mu + b_u + bh_j + U_u · V_j``.  The
reference computes it in plain `jax.numpy` from the benchmark's own
catalog arrays: for given (user, item) pairs, and as the exact top-N over
the whole catalog, user block by user block.  ``dtype`` is float32 at
HIGHEST precision for the reference; the control passes bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("dtype",))
def pair_scores(U, V, bh, mu, b, users, items, *, dtype=jnp.float32):
    """[n] users, [n, T] items → [n, T] scores."""
    c = lambda a: a.astype(dtype)
    u = c(U)[users]                                   # [n, F]
    v = c(V)[items]                                   # [n, T, F]
    dot = jnp.einsum("nf,ntf->nt", u, v, precision=HIGHEST,
                     preferred_element_type=dtype)
    return (c(mu) + c(b)[users][:, None] + c(bh)[items] + dot).astype(
        jnp.float32)


@partial(jax.jit, static_argnames=("topn", "dtype"))
def _block_topn(U, V, bh, mu, b, users, *, topn: int, dtype):
    c = lambda a: a.astype(dtype)
    s = (c(mu) + c(b)[users][:, None] + c(bh)[None, :]
         + jnp.dot(c(U)[users], c(V).T, precision=HIGHEST,
                   preferred_element_type=dtype))
    return jax.lax.top_k(s.astype(jnp.float32), topn)[1]


def exact_topn(U, V, bh, mu, b, users, *, topn: int, block: int = 256,
               dtype=jnp.float32) -> np.ndarray:
    """Exact top-``topn`` item ids over the whole catalog for ``users``."""
    users = np.asarray(users, np.int32)
    pad = (-users.size) % block
    up = np.concatenate([users, np.zeros(pad, np.int32)])
    out = [np.asarray(_block_topn(U, V, bh, mu, b, jnp.asarray(up[s:s + block]),
                                  topn=topn, dtype=dtype))
           for s in range(0, up.size, block)]
    return np.concatenate(out)[:users.size]
