"""Pallas TPU kernel: in-kernel candidate gather + score + top-N.

Serving hot path.  Candidate *ids* enter the kernel (scalar-prefetched
into SMEM); the packed serve plane ``[N, W] = V‖b̂‖0`` stays in HBM
(`pl.ANY`) and each user's C candidate rows are DMA'd into a VMEM
scratch tile on demand — the ``[B, C, F]`` candidate-factor cube that the
PR 1 scorer materialized via an XLA gather (25–38 MB per 256-user flush
at C=512–768, F=48) never exists in HBM.  The gather is double-buffered
across users: while user ``b``'s scores are computed, user ``b+1``'s rows
are already in flight (the embedding-gather analogue of the guide's
double-buffering pattern).  A row DMA must move whole 128-lane tiles, so
the plane is lane-padded to ``W`` (a multiple of 128) with zeros —
`model.pack_serve_planes(lanes=128)` builds it that way once; any other
width is padded here per call.

Per user the score is Eq. (1)'s serving part

    s[c] = (μ + b_i) + b̂[cand[c]] + u · v[cand[c]]

computed as one MXU product of the user row ``u‖1‖0`` against the
``[C, W]`` row tile (the 1 picks up b̂), plus ``μ + b_i`` from a
``[tile_b, 1]`` column.  Masked (SENTINEL-padded) slots score NEG; top-N
is a static-depth iterative argmax (first-index tie rule, matching `jax.lax.top_k`), computed on the
``[1, C]`` row while it is still VMEM-resident.  The winners collect in
register-resident ``[tile_b, topn]`` tiles that are stored once per grid
step.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# python floats (not jnp scalars): they must enter the kernel as literals,
# pallas_call rejects captured traced constants
NEG = -3e38     # effective -inf that survives f32 arithmetic
_NEG2 = -3.4e38  # knock-out value, strictly below NEG so already-selected
                 # (incl. masked) slots never repeat


def _gather_score_kernel(cand_ref, uq_ref, bu_ref, mask_ref, plane_ref,
                         score_out, idx_out, rows, sem, *,
                         topn: int, tile_b: int):
    """cand_ref [Bp, C] int32 in SMEM (scalar prefetch); uq_ref
    [tile_b, W] VMEM (u‖1‖0); bu_ref [tile_b, 1] VMEM (μ + b_i); mask_ref
    [tile_b, C] VMEM; plane_ref [N, W] in ANY/HBM; rows [2, C, W] VMEM
    scratch (double buffer); sem [2] DMA."""
    C = mask_ref.shape[1]
    base = pl.program_id(0) * tile_b

    def row_dma(slot, b, c):
        # one serve-plane row, HBM → the slot's scratch tile
        return pltpu.make_async_copy(
            plane_ref.at[pl.ds(cand_ref[base + b, c], 1)],
            rows.at[slot, pl.ds(c, 1)], sem.at[slot])

    def start_user(slot, b):
        jax.lax.fori_loop(
            0, C, lambda c, _: (row_dma(slot, b, c).start(), 0)[1], 0)

    def wait_user(slot, b):
        # waits are per-copy on the slot's shared semaphore
        jax.lax.fori_loop(
            0, C, lambda c, _: (row_dma(slot, b, c).wait(), 0)[1], 0)

    start_user(0, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (tile_b, topn), 0)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tile_b, topn), 1)

    def user_body(b, acc):
        top_s, top_i = acc
        slot = jax.lax.rem(b, 2)

        @pl.when(b + 1 < tile_b)
        def _():  # prefetch the next user's rows into the other buffer
            start_user(1 - slot, b + 1)

        wait_user(slot, b)
        s = jax.lax.dot_general(                                # [1, C]
            uq_ref[pl.ds(b, 1), :], rows[slot], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        s = s + bu_ref[pl.ds(b, 1), :]
        s = jnp.where(mask_ref[pl.ds(b, 1), :] > 0, s, NEG)
        for t in range(topn):          # static unroll, same as PR 1 kernel
            m = jnp.max(s, axis=1, keepdims=True)               # [1, 1]
            at = jnp.min(jnp.where(s == m, col, C), axis=1, keepdims=True)
            hit = (out_row == b) & (out_col == t)
            top_s = jnp.where(hit, m, top_s)
            top_i = jnp.where(hit, at, top_i)
            s = jnp.where(col == at, _NEG2, s)
        return top_s, top_i

    top_s, top_i = jax.lax.fori_loop(
        0, tile_b, user_body,
        (jnp.zeros((tile_b, topn), jnp.float32),
         jnp.zeros((tile_b, topn), jnp.int32)))
    score_out[...] = top_s
    idx_out[...] = top_i


@functools.partial(jax.jit,
                   static_argnames=("topn", "tile_b", "interpret"))
def candidate_score_topn(urow, plane, cand, mask, *, topn: int,
                         interpret: bool, tile_b: int = 8):
    """urow [B, F+1] (U‖(μ+b) rows); plane [N, ≥F+1] (V‖b̂, zero lanes
    past F+1 allowed); cand [B, C] int32 ids pre-clipped to [0, N); mask
    [B, C] f32 (1.0 valid) → (scores [B, topn] f32, idx [B, topn] int32
    slots into C).

    Masked slots (and padded rows) surface as NEG scores in candidate-slot
    order, exactly like the ref's `top_k` over the masked matrix — callers
    translate idx through their candidate id table and mask on score > NEG.
    ``tile_b`` is rounded up to the 8-row sublane tile.
    """
    B, C = cand.shape
    assert C >= topn, "need at least topn candidate slots"
    F = urow.shape[1] - 1
    lane_pad = (-plane.shape[1]) % 128
    if lane_pad:
        plane = jnp.pad(plane, ((0, 0), (0, lane_pad)))
    W = plane.shape[1]
    uq = jnp.concatenate([urow[:, :F], jnp.ones((B, 1), jnp.float32),
                          jnp.zeros((B, W - F - 1), jnp.float32)], axis=1)
    bu = urow[:, F:]
    tile_b = -(-tile_b // 8) * 8
    pad = (-B) % tile_b
    if pad:
        uq, bu, cand, mask = (jnp.pad(a, ((0, pad), (0, 0)))
                              for a in (uq, bu, cand, mask))
    Bp = uq.shape[0]

    blk = lambda w: pl.BlockSpec((tile_b, w), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # cand ids → SMEM
        grid=(Bp // tile_b,),
        in_specs=[blk(W), blk(1), blk(C),
                  pl.BlockSpec(memory_space=pl.ANY)],  # plane in HBM
        out_specs=[blk(topn), blk(topn)],
        scratch_shapes=[pltpu.VMEM((2, C, W), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    scores, idx = pl.pallas_call(
        functools.partial(_gather_score_kernel, topn=topn, tile_b=tile_b),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bp, topn), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, topn), jnp.int32)],
        interpret=interpret,
    )(cand, uq, bu, mask, plane)
    return scores[:B], idx[:B]
