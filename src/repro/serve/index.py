"""Bucketed LSH index — the serving-side image of the paper's hash table.

`core/topk.py` finds bucket-mates with a *per-call* argsort of every band's
signatures — fine for one-shot Top-K construction, but serving needs a
persistent structure that is built once and probed millions of times.  This
module stores each band's signatures in sorted order with CSR-style bucket
offsets, so a probe is a binary search (or an O(1) slot lookup for items the
index already contains) instead of an O(N log N) sort.

Layout per band b (all fixed-shape, jit-friendly, int32):

  sorted_sigs[b]  [N]  band signatures ascending      ┐ the "CSR" arrays:
  sorted_ids[b]   [N]  item id occupying each slot    │ a bucket is the
  bucket_lo[b]    [N]  first slot of the slot's bucket│ contiguous slot range
  bucket_hi[b]    [N]  one-past-last slot of bucket   ┘ [lo, hi)
  slot_of[b]      [N]  item id → its slot (inverse permutation)

Online ingestion (paper Alg. 4): new items are appended to a small *tail*
buffer that probes scan linearly; when the tail fills up the index is rebuilt
from the full signature set.  This is the classic main+delta ANN design — the
sorted core stays immutable (warm jit caches, no re-sort per insert) and the
tail bounds the extra probe cost.

All candidate outputs are SENTINEL-padded (same convention as `core/topk.py`).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topk import SENTINEL

# tail slots that hold no item: their signature must never match a probe.
# Signatures are packed into ≤30 bits (simlsh.SimLSHConfig.__post_init__),
# so int32 min is unreachable as a real signature.
_EMPTY_SIG = jnp.iinfo(jnp.int32).min


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LSHIndex:
    sorted_sigs: jax.Array   # [q, N] int32
    sorted_ids: jax.Array    # [q, N] int32
    bucket_lo: jax.Array     # [q, N] int32
    bucket_hi: jax.Array     # [q, N] int32
    slot_of: jax.Array       # [q, N] int32
    tail_sigs: jax.Array     # [q, T] int32 (_EMPTY_SIG where unused)
    tail_ids: jax.Array      # [T] int32 (SENTINEL where unused)
    tail_len: jax.Array      # [] int32
    n_base: int = dataclasses.field(metadata=dict(static=True))
    tail_cap: int = dataclasses.field(metadata=dict(static=True))

    @property
    def q(self) -> int:
        return self.sorted_sigs.shape[0]

    @property
    def tail_fill(self) -> int:
        """Host-side tail occupancy.  `build_index`/`insert`/`rebuild`
        maintain a plain-int mirror of ``tail_len`` outside the pytree
        (static fields would retrace every jitted consumer on each
        insert), so the ingestion-plane checks (`needs_rebuild`,
        `n_items`) don't force a device sync per call.  Instances that
        crossed a jit boundary lose the mirror and fall back to one
        sync."""
        t = getattr(self, "_tail_host", None)
        return int(self.tail_len) if t is None else t

    @property
    def n_items(self) -> int:
        """Total items the index can answer for (base + current tail)."""
        return self.n_base + self.tail_fill


def _build_arrays(sigs: jax.Array):
    """The per-band CSR arrays for one signature matrix [q, N] →
    (sorted_sigs, sorted_ids, bucket_lo, bucket_hi, slot_of), all [q, N].
    Shared by the single-device build and the vmapped per-shard build."""
    N = sigs.shape[1]

    def one_band(sig):
        order = jnp.argsort(sig).astype(jnp.int32)
        ssig = sig[order]
        slot_of = jnp.zeros((N,), jnp.int32).at[order].set(
            jnp.arange(N, dtype=jnp.int32))
        lo = jnp.searchsorted(ssig, ssig, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(ssig, ssig, side="right").astype(jnp.int32)
        return ssig, order, lo, hi, slot_of

    return jax.vmap(one_band)(sigs)


@partial(jax.jit, static_argnames=("tail_cap",))
def _build(sigs: jax.Array, tail_cap: int) -> LSHIndex:
    q, N = sigs.shape
    ssig, order, lo, hi, slot_of = _build_arrays(sigs)
    return LSHIndex(
        sorted_sigs=ssig, sorted_ids=order, bucket_lo=lo, bucket_hi=hi,
        slot_of=slot_of,
        tail_sigs=jnp.full((q, tail_cap), _EMPTY_SIG, jnp.int32),
        tail_ids=jnp.full((tail_cap,), SENTINEL, jnp.int32),
        tail_len=jnp.asarray(0, jnp.int32),
        n_base=N, tail_cap=tail_cap)


def build_index(sigs: jax.Array, *, tail_cap: int = 1024) -> LSHIndex:
    """sigs [q, N] int32 (from `core.simlsh.encode`) → persistent index.

    Item ids are the column positions 0..N-1 — the same id space as the
    factor matrix V, so lookups compose directly with scoring.
    """
    # raises (not asserts — these guard data integrity and must survive
    # ``python -O``): a float signature matrix means NaN poisoning
    # upstream; anything non-int32 would be silently reinterpreted by the
    # CSR layout's int32 contract
    if sigs.dtype != jnp.int32:
        hint = (" (float signatures usually mean a NaN-poisoned pipeline "
                "— pass simlsh.pack_bits output)"
                if jnp.issubdtype(sigs.dtype, jnp.floating) else "")
        raise TypeError(f"build_index: signatures must be int32, got "
                        f"{sigs.dtype}{hint}")
    if sigs.ndim != 2:
        raise ValueError(f"build_index: expected [q, N] signatures, got "
                         f"shape {sigs.shape}")
    # retrieve.dedup_candidates runs ids through an invertible
    # multiplicative hash mod 2³⁰ — ids at or above 2³⁰ would silently
    # alias in the dedup, so refuse them at build time
    if sigs.shape[1] > 1 << 30:
        raise ValueError(f"build_index: item ids must stay below 2^30 (the "
                         f"dedup hash mask); got N={sigs.shape[1]}")
    idx = _build(sigs, tail_cap=tail_cap)
    object.__setattr__(idx, "_tail_host", 0)
    return idx


def insert(index: LSHIndex, new_sigs: jax.Array, new_ids: jax.Array) -> LSHIndex:
    """Append new items (Alg. 4 online ingestion) to the tail buffer.

    ``new_sigs`` [q, n] are the re-signed signatures of the *new* columns
    (from `simlsh.update_accumulators`); ``new_ids`` [n] their global ids.
    Raises if the tail would overflow — callers should then `rebuild` with
    the full signature set (see `needs_rebuild`).
    """
    n = int(new_ids.shape[0])
    tl = index.tail_fill
    if tl + n > index.tail_cap:
        raise ValueError(
            f"tail overflow ({tl}+{n} > {index.tail_cap}): rebuild the index")
    # id contract (non-negative ints below the 2^30 dedup hash mask):
    # checked here for host arrays; device arrays skip it rather than
    # force an ingestion-plane sync — their callers assert the bound
    # host-side instead (`build_index`/`rebuild` on N;
    # `ingest_online_update` on state.N, plus the service's
    # check_ingest_batch at the boundary)
    if n and isinstance(new_ids, (np.ndarray, list, tuple)):
        from repro.resil.validate import check_ids   # lazy: keep index.py
        check_ids(new_ids, what="insert new_ids")    # import-light
    if new_sigs is not None and hasattr(new_sigs, "dtype") \
            and np.issubdtype(np.dtype(new_sigs.dtype), np.floating):
        raise TypeError(
            f"insert: signatures must be int32, got {new_sigs.dtype} — "
            f"float signatures usually mean a NaN-poisoned pipeline")
    tail_sigs = jax.lax.dynamic_update_slice(
        index.tail_sigs, jnp.asarray(new_sigs, jnp.int32), (0, tl))
    tail_ids = jax.lax.dynamic_update_slice(
        index.tail_ids, jnp.asarray(new_ids, jnp.int32), (tl,))
    out = dataclasses.replace(
        index, tail_sigs=tail_sigs, tail_ids=tail_ids,
        tail_len=jnp.asarray(tl + n, jnp.int32))
    object.__setattr__(out, "_tail_host", tl + n)
    return out


def needs_rebuild(index: LSHIndex, incoming: int = 0) -> bool:
    return index.tail_fill + incoming > index.tail_cap


def rebuild(index: LSHIndex, sigs: jax.Array) -> LSHIndex:
    """Fold the tail back into the sorted core from the full [q, N'] sigs."""
    return build_index(sigs, tail_cap=index.tail_cap)


def _sig_of_items(index: LSHIndex, ids: jax.Array) -> jax.Array:
    """Band signatures for item ids that live in the index.  ids [...] →
    [q, ...]; unknown/SENTINEL ids get _EMPTY_SIG (match nothing)."""
    in_base = (ids >= 0) & (ids < index.n_base)
    safe = jnp.clip(ids, 0, index.n_base - 1)
    base_sig = index.sorted_sigs[
        jnp.arange(index.q)[:, None], index.slot_of[:, safe.reshape(-1)]
    ].reshape((index.q,) + ids.shape)

    # tail path: linear match over the (small) tail buffer
    tmatch = index.tail_ids[None, :] == ids.reshape(-1)[:, None]   # [Q, T]
    tslot = jnp.argmax(tmatch, axis=1)                             # [Q]
    thit = jnp.any(tmatch, axis=1)
    tail_sig = index.tail_sigs[:, tslot].reshape((index.q,) + ids.shape)
    thit = thit.reshape(ids.shape)

    sig = jnp.where(in_base, base_sig,
                    jnp.where(thit, tail_sig, _EMPTY_SIG))
    return sig


@partial(jax.jit, static_argnames=("cap", "n_probe"))
def lookup_signatures(index: LSHIndex, qsigs: jax.Array, *,
                      cap: int, n_probe: int = 1) -> jax.Array:
    """Probe with explicit band signatures.  qsigs [B, q] → cand [B, L] int32
    with L = q·n_probe·cap + q·cap (tail), SENTINEL-padded.

    Multi-probe: probe t ∈ [0, n_probe) XORs bit (t−1) into the query
    signature (probe 0 is the exact bucket) — the standard single-bit-flip
    probe sequence that trades a few extra binary searches for recall.
    """
    B, q = qsigs.shape
    probe_masks = jnp.asarray(
        [0] + [1 << t for t in range(n_probe - 1)], jnp.int32)    # [n_probe]

    def one_band(ssig, sids, qsig):
        # qsig [B] → probed [B, n_probe]
        probed = qsig[:, None] ^ probe_masks[None, :]
        lo = jnp.searchsorted(ssig, probed.reshape(-1)).astype(jnp.int32)
        pos = lo[:, None] + jnp.arange(cap, dtype=jnp.int32)      # [B·P, cap]
        ok = pos < ssig.shape[0]
        pos = jnp.clip(pos, 0, ssig.shape[0] - 1)
        ok &= ssig[pos] == probed.reshape(-1)[:, None]
        out = jnp.where(ok, sids[pos], SENTINEL)
        return out.reshape(B, n_probe * cap)

    core = jax.vmap(one_band)(index.sorted_sigs, index.sorted_ids,
                              qsigs.T)                            # [q, B, P·cap]
    core = jnp.transpose(core, (1, 0, 2)).reshape(B, -1)

    def one_band_tail(tsig, qsig):
        return _tail_matches(index, tsig, qsig, width=cap)

    tail = jax.vmap(one_band_tail)(index.tail_sigs, qsigs.T)      # [q, B, cap]
    tail = jnp.transpose(tail, (1, 0, 2)).reshape(B, -1)
    return jnp.concatenate([core, tail], axis=1)


@partial(jax.jit, static_argnames=("cap",))
def window_slices(index: LSHIndex, item_ids: jax.Array, *, cap: int):
    """Per-(item, band) bucket-window *descriptors* instead of gathered ids.

    item_ids [B, S] → (starts, lens), both [B, q·S] int32.  ``starts`` are
    flat positions into ``sorted_ids.reshape(-1)`` (band b's slots occupy
    [b·N, (b+1)·N)); ``lens`` ∈ [0, cap] is the number of valid slots from
    the start.  Same geometry as `lookup_items`: the window is centred on
    the item's own slot and clipped to its bucket, so it always contains
    the item itself.  Invalid (SENTINEL / out-of-range / tail-resident)
    items get length 0.

    This is the read contract of the `lsh_retrieve` path
    (`kernels.lsh_retrieve.ref.window_pool`): each descriptor is one
    static ``cap``-wide read of the flat id plane, masked to ``lens``.  A
    read may therefore cover up to ``cap − len`` slots past the window
    (and, in the last band's last bucket, past the array) — consumers
    must read ``sorted_ids`` through `padded_flat_ids`, which appends
    ``cap`` SENTINEL slots so the overrun is always in-bounds and inert.
    """
    B, S = item_ids.shape
    q, Nn = index.q, index.n_base
    valid = (item_ids != SENTINEL) & (item_ids >= 0) & (item_ids < Nn)
    safe = jnp.clip(item_ids, 0, Nn - 1)
    base = (jnp.arange(q, dtype=jnp.int32) * Nn)[:, None, None]    # [q,1,1]
    slot = index.slot_of.reshape(-1)[base + safe[None]]            # [q,B,S]
    fslot = base + slot
    lo = index.bucket_lo.reshape(-1)[fslot]
    hi = index.bucket_hi.reshape(-1)[fslot]
    st = jnp.clip(slot - cap // 2, lo, jnp.maximum(hi - cap, lo))
    ln = jnp.where(valid[None], jnp.minimum(st + cap, hi) - st, 0)
    st = jnp.where(valid[None], st + base, 0)
    starts = jnp.transpose(st, (1, 0, 2)).reshape(B, q * S)
    lens = jnp.transpose(ln, (1, 0, 2)).reshape(B, q * S)
    return starts, lens


@partial(jax.jit, static_argnames=("cap",))
def padded_flat_ids(index: LSHIndex, *, cap: int) -> jax.Array:
    """``sorted_ids`` flattened to [q·N + cap] with a SENTINEL apron, so a
    static ``cap``-wide read at any `window_slices` start stays in-bounds
    (the apron slots hash to padding in the dedup even if a mask slips).
    Cache the result per index version — it copies the whole id plane."""
    return jnp.concatenate(
        [index.sorted_ids.reshape(-1),
         jnp.full((cap,), SENTINEL, jnp.int32)])


def _tail_matches(index: LSHIndex, tsig: jax.Array, qsig: jax.Array, *,
                  width: int) -> jax.Array:
    """Up to ``width`` tail ids whose band signature equals qsig.  [B] →
    [B, width].  Sort-compaction (match positions first) — `top_k` is far
    slower than sort on both CPU and TPU for these shapes."""
    T = tsig.shape[0]
    match = tsig[None, :] == qsig[:, None]                        # [B, T]
    key = jnp.where(match, jnp.arange(T, dtype=jnp.int32), T)
    key = jnp.sort(key, axis=1)[:, :min(width, T)]
    ids = index.tail_ids[jnp.clip(key, 0, T - 1)]
    return jnp.where(key < T, ids, SENTINEL)


@partial(jax.jit, static_argnames=("cap", "include_tail", "assume_base"))
def lookup_items(index: LSHIndex, item_ids: jax.Array, *, cap: int,
                 include_tail: bool = True,
                 assume_base: bool = False) -> jax.Array:
    """Bucket-mates of items already in the index.  item_ids [B] →
    cand [B, q·cap (+ q·cap tail)] int32, SENTINEL-padded (includes the item
    itself).  ``include_tail=False`` skips the tail scan — callers that batch
    many queries per user (see `retrieve.retrieve_for_users`) scan the tail
    once per user instead.  ``assume_base=True`` additionally promises every
    valid query id lives in the sorted core (true whenever the tail is
    empty, `index.tail_fill == 0`), which skips the signature-probe
    fallback below — per-query work drops to the O(1) slot lookup.

    For base items the bucket is addressed by the precomputed slot (no
    binary search); the window is centred on the item's own slot so huge
    buckets spread their mates instead of always returning the bucket head —
    the same windowing `topk.band_candidates` applies.
    """
    B = item_ids.shape[0]
    valid_q = item_ids != SENTINEL
    in_base = valid_q & (item_ids >= 0) & (item_ids < index.n_base)
    safe = jnp.clip(item_ids, 0, index.n_base - 1)

    def one_band(ssig, sids, lo_a, hi_a, slot_of):
        slot = slot_of[safe]                                      # [B]
        lo, hi = lo_a[slot], hi_a[slot]
        start = jnp.clip(slot - cap // 2, lo, jnp.maximum(hi - cap, lo))
        pos = start[:, None] + jnp.arange(cap, dtype=jnp.int32)   # [B, cap]
        ok = in_base[:, None] & (pos < hi[:, None])
        pos = jnp.clip(pos, 0, ssig.shape[0] - 1)
        return jnp.where(ok, sids[pos], SENTINEL)

    core = jax.vmap(one_band)(index.sorted_sigs, index.sorted_ids,
                              index.bucket_lo, index.bucket_hi,
                              index.slot_of)                      # [q, B, cap]

    if not assume_base:
        qsigs = _sig_of_items(index, item_ids)                    # [q, B]

        # tail-resident query items have no slot — find their base bucket
        # by binary search on the signature instead
        def one_band_sig(ssig, sids, qsig):
            lo = jnp.searchsorted(ssig, qsig).astype(jnp.int32)
            pos = lo[:, None] + jnp.arange(cap, dtype=jnp.int32)  # [B, cap]
            ok = pos < ssig.shape[0]
            pos = jnp.clip(pos, 0, ssig.shape[0] - 1)
            ok &= ssig[pos] == qsig[:, None]
            return jnp.where(ok, sids[pos], SENTINEL)

        by_sig = jax.vmap(one_band_sig)(index.sorted_sigs, index.sorted_ids,
                                        qsigs)                    # [q, B, cap]
        core = jnp.where(in_base[None, :, None], core, by_sig)
    core = jnp.transpose(core, (1, 0, 2)).reshape(B, -1)
    if not include_tail:
        return core

    if assume_base:                     # tail scan still requested — the
        qsigs = _sig_of_items(index, item_ids)   # promise only covers the
                                                 # query ids, not the tail

    # tail members that share any band signature with the query item
    def one_band_tail(tsig, qsig):
        return _tail_matches(index, tsig, qsig, width=cap)

    tail = jax.vmap(one_band_tail)(index.tail_sigs, qsigs)        # [q, B, cap]
    tail = jnp.transpose(tail, (1, 0, 2)).reshape(B, -1)
    return jnp.concatenate([core, tail], axis=1)


# ---------------------------------------------------------------------------
# Sharded index — the mesh-partitioned image of the structure above.
#
# For catalogs that outgrow one device the item axis is cut into D
# nnz-balanced contiguous ranges (the scheduler's `balanced_bounds` cuts,
# so "balanced" means the same thing in training and serving) and every
# shard builds the SAME per-band CSR layout over its own items in a
# *local* id space 0..n_d−1.  Shards are block-padded to a common extent
# (the `block_id_map` trick from the training tier): padding slots carry
# `_EMPTY_SIG`, which sorts before every real signature and can never
# match a probe, so they form one inert bucket at the front of each band.
# The stacked [D, ...] arrays shard over `launch.mesh.make_shard_mesh`'s
# "shard" axis with no resharding — leading-axis slice d IS device d's
# local index.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedLSHIndex:
    """Per-shard bucket CSR over local ids, stacked on a leading shard
    axis.  Global id ``g`` of shard ``d`` (``bounds[d] ≤ g < bounds[d+1]``)
    appears as local id ``g − bounds[d]``; local ids ≥ ``n_local[d]`` are
    padding.  No tail: the sharded path serves the offline-bulk regime
    (online inserts go through the single-device tail + rebuild path)."""

    sorted_sigs: jax.Array   # [D, q, block] int32, ascending per band
    sorted_ids: jax.Array    # [D, q, block] int32 local ids
    bucket_lo: jax.Array     # [D, q, block] int32
    bucket_hi: jax.Array     # [D, q, block] int32
    slot_of: jax.Array       # [D, q, block] int32 local id → slot
    n_local: jax.Array       # [D] int32 real (non-padding) items per shard
    bounds: jax.Array        # [D+1] int32 global cut points
    n_items: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))

    @property
    def shards(self) -> int:
        return self.sorted_sigs.shape[0]

    @property
    def q(self) -> int:
        return self.sorted_sigs.shape[1]


def shard_bounds(counts: np.ndarray, shards: int) -> np.ndarray:
    """nnz-balanced item cuts for the serving shards.  ``counts [N]`` are
    per-item rating counts (col degrees); returns ``bounds [D+1]``.  The
    extent floor of N/(4·D) bounds the block padding waste at ~4× even on
    zipf catalogs whose head shard would otherwise collapse to a handful
    of very popular items."""
    from repro.data.sparse import balanced_bounds   # lazy: keep index.py
    N, D = len(counts), shards                      # import-light
    return balanced_bounds(np.asarray(counts), D,
                           floor=max(1, N // (4 * max(D, 1))))


def signatures_of(index: LSHIndex) -> jax.Array:
    """Recover the full [q, n_base] signature matrix from a built index
    (``sigs[b, g] = sorted_sigs[b, slot_of[b, g]]``).  Lets the sharded
    build start from an already-built single-device index without the
    caller re-threading the raw `simlsh.encode` output."""
    return jnp.take_along_axis(index.sorted_sigs, index.slot_of, axis=1)


def build_sharded_index(sigs: jax.Array, *, shards: int,
                        counts: np.ndarray | None = None,
                        bounds: np.ndarray | None = None) -> ShardedLSHIndex:
    """sigs [q, N] int32 → block-padded per-shard CSR stack.

    ``bounds`` (explicit cuts) wins over ``counts`` (nnz-balanced cuts via
    `shard_bounds`); with neither, shards cut the id range evenly.  The
    same dtype/id-space guards as `build_index` apply.
    """
    if sigs.dtype != jnp.int32:
        raise TypeError(f"build_sharded_index: signatures must be int32, "
                        f"got {sigs.dtype}")
    if sigs.ndim != 2:
        raise ValueError(f"build_sharded_index: expected [q, N] signatures, "
                         f"got shape {sigs.shape}")
    q, N = sigs.shape
    if N > 1 << 30:
        raise ValueError(f"build_sharded_index: item ids must stay below "
                         f"2^30 (the dedup hash mask); got N={N}")
    if shards < 1 or N < shards:
        raise ValueError(f"build_sharded_index: need 1 ≤ shards ≤ N, got "
                         f"shards={shards}, N={N}")
    if bounds is None:
        bounds = (shard_bounds(counts, shards) if counts is not None else
                  np.linspace(0, N, shards + 1).astype(np.int64))
    bounds = np.asarray(bounds, np.int64)
    if (len(bounds) != shards + 1 or bounds[0] != 0 or bounds[-1] != N
            or np.any(np.diff(bounds) < 1)):
        raise ValueError(f"build_sharded_index: bounds {bounds} must be "
                         f"strictly increasing from 0 to N={N}")
    ext = np.diff(bounds)
    block = int(ext.max())
    parts = [jnp.pad(sigs[:, int(bounds[d]):int(bounds[d + 1])],
                     ((0, 0), (0, block - int(ext[d]))),
                     constant_values=int(_EMPTY_SIG))
             for d in range(shards)]
    ssig, sids, lo, hi, slot = jax.vmap(_build_arrays)(jnp.stack(parts))
    return ShardedLSHIndex(
        sorted_sigs=ssig, sorted_ids=sids, bucket_lo=lo, bucket_hi=hi,
        slot_of=slot, n_local=jnp.asarray(ext, jnp.int32),
        bounds=jnp.asarray(bounds, jnp.int32), n_items=N, block=block)


def shard_local_view(index: ShardedLSHIndex, d: int) -> LSHIndex:
    """Shard ``d``'s arrays as a plain (tail-less) `LSHIndex` over its
    ``block`` local ids — padding slots included as real `_EMPTY_SIG`
    items.  Host-side tool for validation and tests; the serving path
    slices the stack inside `shard_map` instead."""
    idx = LSHIndex(
        sorted_sigs=index.sorted_sigs[d], sorted_ids=index.sorted_ids[d],
        bucket_lo=index.bucket_lo[d], bucket_hi=index.bucket_hi[d],
        slot_of=index.slot_of[d],
        tail_sigs=jnp.full((index.q, 0), _EMPTY_SIG, jnp.int32),
        tail_ids=jnp.full((0,), SENTINEL, jnp.int32),
        tail_len=jnp.asarray(0, jnp.int32),
        n_base=index.block, tail_cap=0)
    object.__setattr__(idx, "_tail_host", 0)
    return idx
