"""Pallas TPU kernel: in-VMEM dedup of the LSH bucket walk → candidate ids.

The other half of the serving hot path.  Window *descriptors* (flat start
+ valid length per (seed, band) bucket window, from
`serve.index.window_slices`) expand into each user's raw pool with one
XLA gather of static ``cap``-wide reads (`ref.window_pool`): a DMA from
the HBM id plane must move whole tiles (1024 ids for a 1-D int32 array),
so an 8-id window cannot be fetched on its own.  The pool is micro-batch
sized (``[B, I·cap + X]``, ~1.3 MB at B=256); the ``[B, ~1100]`` host-side
dedup sort of the legacy path never exists.

The kernel takes 8 users per grid step as a lane-dense ``[8, Wp]`` tile
(Wp the pool width rounded up to a power of two ≥ 128).  In VMEM the pool
has its exclusions knocked out and is pushed through the same invertible
30-bit multiplicative hash `retrieve.dedup_candidates` uses.  Dedup is
two bitonic sorting networks along the lanes: sort once (duplicate hashes
become adjacent — the hash is injective on [0, 2³⁰)), mark repeats as
INTMAX padding, sort again to compact, unhash the first C.  Each
compare-exchange stage is a pair of lane rotations plus min/max/select
over the whole tile — no data-dependent control flow.  Output is exactly
the `ref.lsh_retrieve_topc_ref` contract — unique ids in hashed order —
so candidate ids can feed the `candidate_score` kernel's scalar-prefetch
operand directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.topk import SENTINEL
from repro.kernels.lsh_retrieve.ref import (INTMAX, INV, MASK30, MULT,
                                            window_pool)

_ROWS = 8          # users per grid step: one sublane tile


def _rotations(x, lane, d):
    """(x rotated by d, x rotated by W - d, lane rotated by d) along the
    lane axis.  Callers pick between the two rotations by where the
    rotated ``lane`` says each value came from, so nothing depends on the
    rotate direction convention."""
    W = x.shape[1]
    return (pltpu.roll(x, d, 1), pltpu.roll(x, W - d, 1),
            pltpu.roll(lane, d, 1))


def _partner(x, lane, j):
    """``x[:, lane ^ j]`` (j a power of two below the width)."""
    fwd, bwd, came_from = _rotations(x, lane, j)
    return jnp.where(came_from == (lane ^ j), fwd, bwd)


def _bitonic_sort_rows(x):
    """Ascending bitonic sort of each row of an int32 [R, W] tile, W a
    power of two.  Fully static: log(W)·(log(W)+1)/2 compare-exchange
    stages, each two lane rotations + min/max/select over the tile."""
    W = x.shape[1]
    assert W & (W - 1) == 0, "bitonic width must be a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 2
    while k <= W:
        j = k // 2
        while j >= 1:
            p = _partner(x, lane, j)
            # a block sorts ascending iff its lanes have bit k clear; the
            # lower lane of a pair keeps the min there, the max elsewhere
            keep_min = ((lane & j) == 0) == ((lane & k) == 0)
            x = jnp.where(keep_min, jnp.minimum(x, p), jnp.maximum(x, p))
            j //= 2
        k *= 2
    return x


def _dedup_kernel(exclude_ref, pool_ref, cand_out, *, C: int, E: int):
    """exclude_ref [E] int32 SMEM (scalar prefetch); pool_ref [8, Wp]
    int32 VMEM (SENTINEL-padded raw pool); cand_out [8, C]."""
    pool = pool_ref[...]
    for e in range(E):             # static unroll over the small exclude set
        pool = jnp.where(pool == exclude_ref[e], SENTINEL, pool)
    valid = (pool != SENTINEL) & (pool >= 0)
    h = jnp.where(valid, (pool * MULT) & MASK30, INTMAX)
    h = _bitonic_sort_rows(h)
    lane = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    fwd, bwd, came_from = _rotations(h, lane, 1)
    prev = jnp.where(came_from == lane - 1, fwd, bwd)     # h[:, lane - 1]
    prev = jnp.where(lane == 0, -1, prev)
    h = jnp.where((h != prev) & (h != INTMAX), h, INTMAX)
    h = _bitonic_sort_rows(h)      # compact survivors left
    keys = h[:, :C]
    cand_out[...] = jnp.where(keys != INTMAX, (keys * INV) & MASK30, SENTINEL)


@functools.partial(jax.jit, static_argnames=("C", "cap", "interpret"))
def lsh_retrieve_topc(starts, lens, extra, ids_flat, exclude, *, C: int,
                      cap: int, interpret: bool):
    """starts/lens [B, I] int32 window descriptors; extra [B, X] int32
    SENTINEL-padded appended ids; ids_flat [q·N + cap] int32
    (`padded_flat_ids` — the apron keeps every cap-wide read in bounds);
    exclude [E] int32 → cand [B, C] int32 unique ids, SENTINEL-padded,
    in hashed order (the `ref.lsh_retrieve_topc_ref` contract)."""
    pool = window_pool(starts, lens, extra, ids_flat, cap=cap)
    B, W = pool.shape
    assert C <= W, f"candidate budget C={C} exceeds pool width {W}"
    Wp = max(128, 1 << (W - 1).bit_length())   # power of two, ≥ one vreg
    pool = jnp.pad(pool, ((0, (-B) % _ROWS), (0, Wp - W)),
                   constant_values=SENTINEL)
    E = exclude.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # exclude → SMEM
        grid=(pool.shape[0] // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, Wp), lambda b, *_: (b, 0))],
        out_specs=pl.BlockSpec((_ROWS, C), lambda b, *_: (b, 0)),
    )
    cand = pl.pallas_call(
        functools.partial(_dedup_kernel, C=C, E=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pool.shape[0], C), jnp.int32),
        interpret=interpret,
    )(exclude, pool)
    return cand[:B]
