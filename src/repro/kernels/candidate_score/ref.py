"""Pure-jnp oracle for the fused candidate-score + top-N kernel.

Mirrors the kernel's in-kernel-gather contract: candidate *ids* come in,
plane rows are fetched per user-tile inside a `lax.scan`, so the gather
intermediate is ``[tile_b, C, F+1]`` — the full ``[B, C, F]`` candidate
cube never appears in the HLO (asserted by
`tests/test_serve.py::test_scorer_hlo_has_no_candidate_cube`).  On CPU
this is also the fast path: a tile's rows stay cache-resident between the
gather and the matvec instead of round-tripping a ~25 MB cube through
memory per flush.
"""
import jax
import jax.numpy as jnp

from repro.kernels.candidate_score.kernel import NEG


def candidate_score_topn_ref(urow, plane, cand, mask, *, topn: int,
                             tile_b: int = 8):
    """urow [B, F+1] (= U‖(μ+b) rows, pre-gathered); plane [N, ≥F+1] =
    V‖b̂ (lanes past F+1 ignored); cand [B, C] int32 ids (pre-clipped to
    [0, N)); mask [B, C] (1.0 valid) → (scores [B, topn] f32, idx
    [B, topn] int32 slots into C)."""
    B, C = cand.shape
    F = urow.shape[1] - 1
    pad = (-B) % tile_b
    if pad:
        urow = jnp.pad(urow, ((0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    T = urow.shape[0] // tile_b

    def tile(_, args):
        u, c, m = args
        rows = plane[c][..., :F + 1]                     # [tile_b, C, F+1]
        s = (jnp.einsum("bf,bcf->bc", u[:, :F], rows[..., :F])
             + rows[..., F] + u[:, F][:, None])
        s = jnp.where(m > 0, s, NEG)
        sc, idx = jax.lax.top_k(s, topn)
        return None, (sc, idx.astype(jnp.int32))

    _, (scores, idx) = jax.lax.scan(
        tile, None, (urow.reshape(T, tile_b, F + 1),
                     cand.reshape(T, tile_b, C), mask.reshape(T, tile_b, C)))
    return scores.reshape(-1, topn)[:B], idx.reshape(-1, topn)[:B]
