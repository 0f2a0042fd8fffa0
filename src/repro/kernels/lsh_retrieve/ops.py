"""jit'd wrapper: seeds → window descriptors → in-VMEM walk + dedup →
[B, C] candidate ids.

The retrieval-side twin of `candidate_score.ops.score_candidates`: the
program builds only micro-batch-sized tensors (descriptors starts/lens
[B, I], tail extras [B, X], the expanded [B, I·cap + X] window pool); the
dedup of the union happens inside the kernel.  The output feeds
`score_candidates`'s scalar-prefetch candidate operand directly, so on
TPU the fused recommend path is two chained kernels in one program.

``impl='ref'`` swaps in the pure-jnp oracle (`ref.lsh_retrieve_topc_ref`)
with the identical contract — the CPU path, where Pallas only has the
(slow) interpreter.  Note the *serving* CPU fast path does not dedup at
all (`service.recommend_walked` defers duplicates to top-n selection);
this wrapper is the contract for accelerators and for parity tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.topk import SENTINEL
from repro.data.sparse import SparseMatrix
from repro.kernels.lsh_retrieve.kernel import lsh_retrieve_topc
from repro.kernels.lsh_retrieve.ref import lsh_retrieve_topc_ref
from repro.serve.index import LSHIndex, padded_flat_ids, window_slices
from repro.serve.retrieve import seed_items, tail_hits


@partial(jax.jit, static_argnames=("n_seeds", "cap", "C", "window",
                                   "tail_scan", "interpret", "impl"))
def retrieve_candidates(index: LSHIndex, sp: SparseMatrix,
                        user_ids: jax.Array, *, n_seeds: int, cap: int,
                        C: int, popular: jax.Array | None = None,
                        interpret: bool, window: int = 64,
                        tail_scan: bool = True, impl: str = "pallas",
                        ids_flat: jax.Array | None = None) -> jax.Array:
    """user_ids [B] → cand [B, C] int32 unique candidate ids,
    SENTINEL-padded.  Same slot layout as `retrieve.finalize_candidates`:
    when ``popular`` [P] is given it occupies reserved trailing slots and
    is excluded from the walked core (inside the kernel, not by a second
    dedup).  ``ids_flat`` lets services pass a cached `padded_flat_ids`
    plane instead of re-concatenating it per flush."""
    seeds = seed_items(sp, user_ids, n_seeds=n_seeds, window=window)
    starts, lens = window_slices(index, seeds, cap=cap)
    B = user_ids.shape[0]
    if tail_scan and index.tail_cap:
        extra = tail_hits(index, seeds)
    else:                          # X ≥ 1 keeps the kernel shape static
        extra = jnp.full((B, 1), SENTINEL, jnp.int32)
    if ids_flat is None:
        ids_flat = padded_flat_ids(index, cap=cap)
    if popular is not None:
        P = popular.shape[0]
        assert C > P, f"candidate budget C={C} must exceed the shortlist {P}"
        exclude, core_C = popular, C - P
    else:
        exclude = jnp.full((1,), SENTINEL, jnp.int32)
        core_C = C
    fn = lsh_retrieve_topc_ref if impl == "ref" else partial(
        lsh_retrieve_topc, interpret=interpret)
    core = fn(starts, lens, extra, ids_flat, exclude, C=core_C, cap=cap)
    if popular is None:
        return core
    return jnp.concatenate(
        [core, jnp.broadcast_to(popular[None, :], (B, P))], axis=1)
