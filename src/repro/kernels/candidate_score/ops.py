"""jit'd wrapper: serve-plane row gather → in-kernel candidate gather +
fused score + top-N.

One gather per side: the *row* plane (`U‖b`, micro-batch-sized) is
gathered here and the μ baseline folded into its bias column; the *col*
plane (`V‖b̂`) is handed to the kernel whole, which fetches candidate
rows by id inside (Pallas DMA gather) or per user-tile (jnp ref scan) —
either way the `[B, C, F]` candidate cube of the PR 1 scorer never
materializes.  The returned top-N slots are translated back to global
item ids, SENTINEL where a slot was padding.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.model import Params, ServePlanes, pack_serve_planes
from repro.core.topk import SENTINEL
from repro.kernels.candidate_score.kernel import NEG, candidate_score_topn
from repro.kernels.candidate_score.ref import candidate_score_topn_ref


@partial(jax.jit, static_argnames=("topn", "tile_b", "interpret", "impl"))
def score_candidates(planes, user_ids: jax.Array, cand: jax.Array, *,
                     topn: int, interpret: bool, tile_b: int = 8,
                     impl: str = "pallas"):
    """planes (`model.ServePlanes`; a `Params` is packed on the fly for
    compatibility), user_ids [B], cand [B, C] SENTINEL-padded →
    (scores [B, topn], items [B, topn] int32, SENTINEL where deficient).

    ``impl='ref'`` runs the pure-jnp tiled-scan oracle instead of the
    Pallas kernel — the fast path on CPU, where Pallas only has the
    (slow) interpreter.
    """
    if isinstance(planes, Params):
        planes = pack_serve_planes(planes)
    F = planes.F
    safe = jnp.clip(cand, 0, planes.n_items - 1)
    mask = (cand != SENTINEL).astype(jnp.float32)
    urow = planes.row[user_ids]                    # ONE row-side gather
    urow = urow.at[:, F].add(planes.mu)            # bias col := μ + b_i
    if impl == "ref":
        scores, idx = candidate_score_topn_ref(urow, planes.col, safe, mask,
                                               topn=topn, tile_b=tile_b)
    else:
        scores, idx = candidate_score_topn(urow, planes.col, safe, mask,
                                           topn=topn, tile_b=tile_b,
                                           interpret=interpret)
    items = jnp.take_along_axis(cand, idx, axis=1)
    items = jnp.where(scores > NEG, items, SENTINEL)
    return scores, items
