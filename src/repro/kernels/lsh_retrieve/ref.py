"""Pure-jnp oracle for the fused LSH-retrieval kernel.

Mirrors the kernel's contract bit for bit: window descriptors come in
(flat starts + valid lengths from `serve.index.window_slices`), each
descriptor is expanded as a static ``cap``-wide read of the padded flat
id plane (`window_pool`, shared with the kernel wrapper), extras (tail
hits) are appended, exclusions and invalid slots are masked, and the
surviving ids are deduplicated through the same invertible 30-bit
multiplicative hash the kernel sorts in VMEM.  The
output is each user's first C unique ids in *hashed* order — identical
to the kernel because both reduce to "sort the same multiset of hash
keys, drop duplicate neighbours, sort again, unhash the first C".

Kept separate from `serve.retrieve`'s walk path on purpose: the walk
path never materialises a dedup at all (duplicates survive to top-n
selection); this oracle exists so interpret-mode kernel tests have an
exact reference for the in-VMEM dedup.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.topk import SENTINEL

# same invertible multiplicative hash pair as retrieve.dedup_candidates:
# h = 2654435761·x mod 2³⁰ (as int32), x = 244002641·h mod 2³⁰
MULT = -1640531535
INV = 244002641
MASK30 = 0x3FFFFFFF
# sort-domain padding: above every 30-bit hash, so padding sinks last
INTMAX = 0x7FFFFFFF


def window_pool(starts, lens, extra, ids_flat, *, cap: int):
    """Expand window descriptors into the raw candidate pool: each
    (starts, lens) pair is a static ``cap``-wide read of the padded flat
    id plane, masked to its valid prefix, with the extras appended →
    [B, I·cap + X] int32, SENTINEL where masked (duplicates intact)."""
    B, I = starts.shape
    pos = starts[:, :, None] + jnp.arange(cap, dtype=jnp.int32)    # [B,I,cap]
    ids = ids_flat[pos]
    ok = jnp.arange(cap, dtype=jnp.int32)[None, None, :] < lens[:, :, None]
    return jnp.concatenate(
        [jnp.where(ok, ids, SENTINEL).reshape(B, I * cap), extra], axis=1)


def lsh_retrieve_topc_ref(starts, lens, extra, ids_flat, exclude, *,
                          C: int, cap: int):
    """starts/lens [B, I] int32 (`window_slices` descriptors); extra
    [B, X] int32 SENTINEL-padded ids appended to the pool (tail hits);
    ids_flat [q·N + cap] int32 (`padded_flat_ids`); exclude [E] int32 ids
    dropped from the output (SENTINEL entries inert) → cand [B, C] int32,
    each user's unique pool ids in hashed order, SENTINEL-padded."""
    B = starts.shape[0]
    pool = window_pool(starts, lens, extra, ids_flat, cap=cap)
    excluded = jnp.any(pool[:, :, None] == exclude[None, None, :], axis=2)
    valid = (pool != SENTINEL) & (pool >= 0) & ~excluded
    h = jnp.where(valid, (pool * jnp.int32(MULT)) & jnp.int32(MASK30),
                  jnp.int32(INTMAX))
    h = jnp.sort(h, axis=1)
    prev = jnp.concatenate([jnp.full((B, 1), -1, h.dtype), h[:, :-1]], axis=1)
    h = jnp.where((h != prev) & (h != INTMAX), h, jnp.int32(INTMAX))
    h = jnp.sort(h, axis=1)[:, :C]
    return jnp.where(h != INTMAX, (h * jnp.int32(INV)) & jnp.int32(MASK30),
                     SENTINEL)
