"""The nonlinear neighbourhood MF model — paper Eq. (1).

r̂_ij = b̄_ij + |R^K(i;j)|^{-1/2} Σ_{j1∈R^K} (r_ij1 − b̄_ij1)·w_{j,k1}
              + |N^K(i;j)|^{-1/2} Σ_{j2∈N^K} c_{j,k2}
              + u_i·v_jᵀ

with the CULSH-MF complement trick (paper §4.2(2)):
S^K(j) = R^K(i;j) ⊎ N^K(i;j) — each of the K neighbours of j is *either*
explicit (i rated it) or implicit, so every sample touches exactly K of the
2K parameters {w_j, c_j}, the load-balance property the CUDA kernel relies
on and that our fused Pallas kernel/TPU batch exploit identically.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import SparseMatrix, baselines, lookup


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Params:
    """Unpacked parameters — the public API layout.

    `FitResult`, checkpoints, `repro.serve` and the online Alg.-4 path all
    speak this layout; the scheduled training hot path packs it into the
    two-plane `PackedParams` (see `pack_params`) and unpacks at the eval /
    checkpoint / result boundary."""

    U: jax.Array   # [M, F]
    V: jax.Array   # [N, F]
    b: jax.Array   # [M]
    bh: jax.Array  # [N]
    W: jax.Array   # [N, K]
    C: jax.Array   # [N, K]
    mu: jax.Array  # []


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedParams:
    """Packed-plane training layout: all row-side parameters in one
    ``[M, F+1]`` plane and all col-side parameters in one ``[N, F+2K+1]``
    plane, so an SGD step is **two** gather/scatter pairs instead of six.

    Column layout (scalars last, so U/V start lane-aligned at 0):

    * ``row[:, :F]`` = U,   ``row[:, F]`` = b
    * ``col[:, :F]`` = V,   ``col[:, F:F+K]`` = W,
      ``col[:, F+K:F+2K]`` = C,   ``col[:, F+2K]`` = b̂

    Every per-sample CULSH-MF update touches one row of each plane (the
    §4.2(2) load-balance property: exactly K of the 2K {w, c} slots, plus
    V/b̂ — all living in the same col-plane row), so the packed scatter
    moves the same payload as the six separate ones in one op each.  Under
    the rotation shard tier the whole row plane ring-`ppermute`s as one
    array (U and b together — one collective per sub-epoch, not two).
    """

    row: jax.Array  # [M, F+1] float32 — U ‖ b
    col: jax.Array  # [N, F+2K+1] float32 — V ‖ W ‖ C ‖ b̂
    mu: jax.Array   # []
    F: int = dataclasses.field(metadata=dict(static=True))
    K: int = dataclasses.field(metadata=dict(static=True))

    @property
    def bh(self) -> jax.Array:
        """The b̂ column (neighbour-baseline snapshots gather from it)."""
        return self.col[:, self.F + 2 * self.K]


def pack_params(p: Params) -> PackedParams:
    """Params → the two training planes (one concatenate per side)."""
    F = int(p.U.shape[1])
    K = int(p.W.shape[1])
    return PackedParams(
        row=jnp.concatenate([p.U, p.b[:, None]], axis=1),
        col=jnp.concatenate([p.V, p.W, p.C, p.bh[:, None]], axis=1),
        mu=p.mu, F=F, K=K)


def unpack_params(pp: PackedParams) -> Params:
    """The inverse of `pack_params` (six column slices)."""
    F, K = pp.F, pp.K
    return Params(U=pp.row[:, :F], V=pp.col[:, :F], b=pp.row[:, F],
                  bh=pp.col[:, F + 2 * K], W=pp.col[:, F:F + K],
                  C=pp.col[:, F + K:F + 2 * K], mu=pp.mu)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServePlanes:
    """Packed *serving* layout: the scoring-relevant parameters as two
    planes, built once per `RecsysService` (the serving analogue of
    `PackedParams`; W/C/μ-neighbour terms never enter the serving score,
    so the col plane is just ``[N, F+1]``).

    * ``row[:, :F]`` = U,  ``row[:, F]`` = b   — one gather per user
      fetches factors *and* bias;
    * ``col[:, :F]`` = V,  ``col[:, F]`` = b̂  — one gather (or one
      in-kernel DMA) per candidate fetches factors *and* item bias.

    `kernels/candidate_score` consumes these directly: the col plane is
    the HBM-resident operand whose rows are gathered *inside* the kernel
    by candidate id, so no ``[B, C, F]`` cube is ever materialized.
    """

    row: jax.Array  # [M, F+1] float32 — U ‖ b
    col: jax.Array  # [N, ≥F+1] float32 — V ‖ b̂ (‖ zero lane padding)
    mu: jax.Array   # []
    F: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_items(self) -> int:
        return self.col.shape[0]


def pack_serve_planes(p: Params, *, lanes: int = 1) -> ServePlanes:
    """Params → the two serving planes (one concatenate per side).

    ``lanes`` rounds the col plane's width up to a multiple with zero
    columns past b̂: the `candidate_score` kernel DMAs whole 128-lane rows,
    so services on that path build it with ``lanes=128`` once instead of
    padding per flush."""
    F = int(p.U.shape[1])
    pad = (-(F + 1)) % lanes
    return ServePlanes(
        row=jnp.concatenate([p.U, p.b[:, None]], axis=1),
        col=jnp.concatenate(
            [p.V, p.bh[:, None], jnp.zeros((p.V.shape[0], pad), p.V.dtype)],
            axis=1),
        mu=p.mu, F=F)


def unpack_serve_planes(sp: ServePlanes) -> Params:
    """Inverse of `pack_serve_planes` — back to the public layout, with
    zero-width W/C planes (the serving score never uses them)."""
    F = sp.F
    N = sp.col.shape[0]
    z = jnp.zeros((N, 0), jnp.float32)
    return Params(U=sp.row[:, :F], V=sp.col[:, :F], b=sp.row[:, F],
                  bh=sp.col[:, F], W=z, C=z, mu=sp.mu)


def shard_col_plane(col: jax.Array, bounds) -> jax.Array:
    """Partition a ``[N, W]`` item plane into block-padded shards.

    ``bounds [D+1]`` are nnz-balanced item cuts (`data.sparse.
    balanced_bounds`): shard ``d`` owns global ids ``[bounds[d],
    bounds[d+1])``.  Returns ``[D, block, W]`` with ``block = max shard
    extent`` — the equal-shape stack `jax.shard_map` needs — where local
    row ``l`` of shard ``d`` is global row ``bounds[d] + l`` and rows past
    the shard's extent are zero (never gathered: the sharded retrieval
    masks local ids ≥ the shard's item count to SENTINEL before scoring).
    """
    bounds = np.asarray(bounds)
    D = len(bounds) - 1
    ext = np.diff(bounds)
    block = int(ext.max())
    parts = [jnp.pad(col[int(bounds[d]):int(bounds[d + 1])],
                     ((0, block - int(ext[d])), (0, 0)))
             for d in range(D)]
    return jnp.stack(parts)


def unshard_col_plane(stack: jax.Array, bounds) -> jax.Array:
    """Inverse of `shard_col_plane`: drop each shard's padding rows and
    concatenate back to the original ``[N, W]`` id order."""
    bounds = np.asarray(bounds)
    ext = np.diff(bounds)
    return jnp.concatenate(
        [stack[d, :int(ext[d])] for d in range(len(ext))])


def remap_params(p: Params, sched) -> Params:
    """Re-lay params from original ids into the schedule's block-padded id
    space (`EpochSchedule.row_map`/``col_map``) — required before training
    on a ``shards > 1`` schedule, whose `ScheduledData`/`ShardData` store
    remapped ids so every parameter block is a contiguous equal-size range
    (the shape `jax.shard_map` needs).  Padded slots (ids no map hits) are
    zero and touched by no triple.  No-op on unsharded schedules."""
    if sched.row_map.size == 0:
        return p
    rm, cm = sched.row_map, sched.col_map
    Mp = sched.shards * sched.block_rows
    Np = sched.shards * sched.block_cols
    scat = lambda a, m, n: jnp.zeros((n,) + a.shape[1:], a.dtype).at[m].set(a)
    return Params(U=scat(p.U, rm, Mp), V=scat(p.V, cm, Np),
                  b=scat(p.b, rm, Mp), bh=scat(p.bh, cm, Np),
                  W=scat(p.W, cm, Np), C=scat(p.C, cm, Np), mu=p.mu)


def unmap_params(p: Params, sched) -> Params:
    """Inverse of `remap_params`: gather the original-id rows back out of
    the block-padded layout (drops the padding slots)."""
    if sched.row_map.size == 0:
        return p
    rm, cm = sched.row_map, sched.col_map
    return Params(U=p.U[rm], V=p.V[cm], b=p.b[rm], bh=p.bh[cm],
                  W=p.W[cm], C=p.C[cm], mu=p.mu)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Batch:
    i: jax.Array        # [B] row ids
    j: jax.Array        # [B] col ids
    r: jax.Array        # [B] ratings
    nb: jax.Array       # [B, K] neighbour ids (J^K[j])
    rnb: jax.Array      # [B, K] r_{i, nb} (0 where unobserved)
    expl: jax.Array     # [B, K] float mask: neighbour in R^K(i;j)
    impl: jax.Array     # [B, K] float mask: neighbour in N^K(i;j)
    valid: jax.Array    # [B] float mask (padding)


def init_params(key, M, N, F, K, mu=0.0, scale=None) -> Params:
    ku, kv = jax.random.split(key)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(F)
    return Params(
        U=jax.random.normal(ku, (M, F), jnp.float32) * scale,
        V=jax.random.normal(kv, (N, F), jnp.float32) * scale,
        b=jnp.zeros((M,), jnp.float32),
        bh=jnp.zeros((N,), jnp.float32),
        W=jnp.zeros((N, K), jnp.float32),
        C=jnp.zeros((N, K), jnp.float32),
        mu=jnp.asarray(mu, jnp.float32),
    )


def init_from_data(key, sp: SparseMatrix, F, K) -> Params:
    mu, b, bh = baselines(sp)
    p = init_params(key, sp.M, sp.N, F, K, mu=0.0)
    return dataclasses.replace(p, mu=mu, b=b, bh=bh)


def assemble(sp: SparseMatrix, JK: jax.Array, idx: jax.Array,
             valid: jax.Array, lookup_sp: SparseMatrix | None = None) -> Batch:
    """Gather everything a training batch needs (rating lookups via the
    sorted-key binary search — the TPU answer to the GPU hash probe).

    ``idx`` indexes ``sp``'s triples; neighbour-rating lookups go against
    ``lookup_sp`` when given (Alg. 4 online: sample ΔΩ, look up in Ω̂)."""
    i, j, r = sp.rows[idx], sp.cols[idx], sp.vals[idx]
    nb = JK[j]                                              # [B, K]
    src = sp if lookup_sp is None else lookup_sp
    rnb, hit = lookup(src, jnp.broadcast_to(i[:, None], nb.shape), nb)
    expl = hit.astype(jnp.float32)
    impl = 1.0 - expl
    return Batch(i, j, r, nb, rnb, expl, impl, valid.astype(jnp.float32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScheduledData:
    """Cf-region training data laid out in `EpochSchedule` order (once per
    fit).

    Every width-tier / leftover batch is a contiguous window of these
    arrays, so batch assembly is a `dynamic_slice` + the schedule's valid
    mask — no per-batch gather at all (`slice_batch`).  Arrays are padded
    by ``sched.pad_width`` slots past the region fill so a window that
    reads past the last batch's fill stays in bounds (the overread is
    masked).  Shard-tier triples (schedule positions ``< shard_span``) are
    **not** here — they live in the dense, device-shardable `ShardData` —
    so on a multi-device mesh the replicated arrays only hold the
    cf-region triples.

    With ``sched.shards > 1`` the ``i``/``j``/``nb`` ids are in the
    schedule's block-padded id space (see `EpochSchedule` — train against
    `remap_params`-relaid parameters).

    The three neighbour planes are stored ``[K, P]`` (one sample per
    lane): on a TPU a ``[P, K]`` plane with K < 128 is either padded to
    128 lanes or re-laid out whole for the kernel, which at 9.9M ratings
    and K=32 is more memory than the chip has.  `slice_batch` hands out
    the usual ``[B, K]`` batch planes.

    For ``mf_only`` fits the neighbour planes are built zero-width: the
    MF step never reads them and the [nnz, K] cache memory is skipped.
    """

    i: jax.Array     # [P] int32 row ids
    j: jax.Array     # [P] int32 col ids
    r: jax.Array     # [P] float32 ratings
    nb: jax.Array    # [K, P] int32 neighbour ids (J^K[j])
    rnb: jax.Array   # [K, P] float32 r_{i, nb} (0 where unobserved)
    expl: jax.Array  # [K, P] float32 explicit-slot mask


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardData:
    """Shard-tier cells as dense ``[D, S, R, Wsh]`` slot arrays.

    Cell ``(d, s, r)`` *is* the batch — no window slicing — and the
    leading axis is the device axis, so under `jax.shard_map` the arrays
    shard with ``P("shard")`` and each device holds exactly its own
    cells' triples (the `ScheduledData` backing arrays used to be
    replicated across the mesh; ROADMAP "shard-tier data sharding").
    Empty slots are masked by ``sched.shard_valid``.  Ids are in the
    block-padded space whenever the schedule's are.
    """

    i: jax.Array     # [D, S, R, W] int32
    j: jax.Array     # [D, S, R, W] int32
    r: jax.Array     # [D, S, R, W] float32
    nb: jax.Array    # [D, S, R, W, K] int32
    rnb: jax.Array   # [D, S, R, W, K] float32
    expl: jax.Array  # [D, S, R, W, K] float32


def _ordered_planes(sp: SparseMatrix, JK: jax.Array, sched, order_ids,
                    pad: int, *, mf_only: bool, chunk: int,
                    lanes: bool = False):
    """One binary-search sweep over ``order_ids``-ordered triples → the
    (i, j, r, nb, rnb, expl) planes padded by ``pad`` zero slots (chunked
    so the [chunk, K, log nnz] search intermediates stay off the
    high-water mark; written in schedule order directly so no second
    permutation pass is needed).  Ids are remapped into the schedule's
    block-padded id space when the schedule carries maps; rating lookups
    always use the original ids.  ``lanes`` builds the neighbour planes
    ``[K, P]`` instead of ``[P, K]``, chunk by chunk."""
    n = int(order_ids.shape[0])
    has_map = sched.row_map.size > 0
    ax = 1 if lanes else 0
    padded = lambda a, axis=0: jnp.concatenate(
        [a, jnp.zeros(a.shape[:axis] + (pad,) + a.shape[axis + 1:], a.dtype)],
        axis=axis)
    ri, cj = sp.rows[order_ids], sp.cols[order_ids]
    i = padded(sched.row_map[ri] if has_map else ri)
    j = padded(sched.col_map[cj] if has_map else cj)
    r = padded(sp.vals[order_ids])
    K = 0 if mf_only else JK.shape[1]
    orient = (lambda a: a.T) if lanes else (lambda a: a)
    parts = ([], [], [])
    for c0 in range(0, n if K else 0, chunk):
        ii, cc = ri[c0:c0 + chunk], cj[c0:c0 + chunk]
        nn = JK[cc]                      # original col ids (for the lookup)
        rnb, hit = lookup(sp, jnp.broadcast_to(ii[:, None], nn.shape), nn)
        for out, a in zip(parts, (sched.col_map[nn] if has_map else nn, rnb,
                                  hit.astype(jnp.float32))):
            out.append(orient(a))
    empty = (K, n + pad) if lanes else (n + pad, K)
    planes = [padded(jnp.concatenate(p, axis=ax), ax) if p
              else jnp.zeros(empty, dt)
              for p, dt in zip(parts, (jnp.int32, jnp.float32, jnp.float32))]
    return (i, j, r, *planes)


def build_scheduled_data(sp: SparseMatrix, JK: jax.Array, sched, *,
                         mf_only: bool = False,
                         chunk: int = 65536) -> ScheduledData:
    """Cf-region (width tiers + leftovers) planes in schedule order —
    see `_ordered_planes`.  Pair with `build_shard_data` when the
    schedule has a shard tier."""
    return ScheduledData(*_ordered_planes(
        sp, JK, sched, sched.order[sched.shard_span:], sched.pad_width,
        mf_only=mf_only, chunk=chunk, lanes=True))


def build_shard_data(sp: SparseMatrix, JK: jax.Array, sched, *,
                     mf_only: bool = False,
                     chunk: int = 65536) -> ShardData | None:
    """Shard-tier cells gathered into the dense ``[D, S, R, Wsh]`` layout
    (None when the schedule has no shard tier)."""
    if sched.shard_span == 0:
        return None
    Wsh = sched.shard_width
    planes = _ordered_planes(sp, JK, sched, sched.order[:sched.shard_span],
                             Wsh, mf_only=mf_only, chunk=chunk)
    idx = sched.shard_starts[..., None] + jnp.arange(Wsh)   # [D, S, R, W]
    return ShardData(*(p[idx] for p in planes))


def slice_batch(sd: ScheduledData, start: jax.Array, width: int,
                valid: jax.Array) -> Batch:
    """Assemble a schedule-window batch: contiguous slices, zero gathers
    (the ``[K, P]`` neighbour planes come out as ``[B, K]``)."""
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, width, axis=0)
    slt = lambda a: jax.lax.dynamic_slice_in_dim(a, start, width, axis=1).T
    expl = slt(sd.expl)
    return Batch(sl(sd.i), sl(sd.j), sl(sd.r), slt(sd.nb), slt(sd.rnb),
                 expl, 1.0 - expl, valid.astype(jnp.float32))


def predict_gathered(mu, b_i, bh_j, ui, vj, wj, cj, bh_of_nb,
                     rnb, expl, impl):
    """Eq. (1) on pre-gathered row-aligned operands — the single forward
    shared by the unpacked `predict`, the packed-plane SGD steps and the
    `kernels/mf_sgd` jnp ref, so the layouts stay bit-identical by
    construction (only the in-Pallas kernel keeps an inline copy)."""
    bbar = mu + b_i + bh_j                                  # [B]
    bbar_nb = mu + b_i[:, None] + bh_of_nb                  # [B, K]
    resid = (rnb - bbar_nb) * expl                          # [B, K]
    nR = jnp.sum(expl, 1)
    nN = jnp.sum(impl, 1)
    sR = jnp.where(nR > 0, jax.lax.rsqrt(jnp.maximum(nR, 1.0)), 0.0)
    sN = jnp.where(nN > 0, jax.lax.rsqrt(jnp.maximum(nN, 1.0)), 0.0)
    expl_term = sR * jnp.sum(resid * wj, 1)
    impl_term = sN * jnp.sum(impl * cj, 1)
    dot = jnp.sum(ui * vj, 1)
    pred = bbar + expl_term + impl_term + dot
    return pred, dict(resid=resid, sR=sR, sN=sN)


def predict(p: Params, bt: Batch, bh_nb: jax.Array | None = None):
    """Eq. (1). Returns (pred [B], aux) with aux reused by the manual SGD.

    ``bh_nb`` optionally substitutes pre-gathered neighbour baselines
    b̂[nb] — the shard-tier scan passes an epoch-start snapshot because
    neighbour cols cross device block boundaries (cuMF-style stale read;
    b̂ drifts one epoch at most)."""
    bh_of_nb = p.bh[bt.nb] if bh_nb is None else bh_nb
    return predict_gathered(p.mu, p.b[bt.i], p.bh[bt.j], p.U[bt.i],
                            p.V[bt.j], p.W[bt.j], p.C[bt.j], bh_of_nb,
                            bt.rnb, bt.expl, bt.impl)


def predict_mf(p: Params, bt: Batch):
    """Plain-MF prediction (the CUSGD++ model): r̂ = u_i·v_j."""
    return jnp.sum(p.U[bt.i] * p.V[bt.j], 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EvalCache:
    """Test-set neighbour gathers, precomputed once per fit.

    `rmse` re-runs the [B, K] binary-search rating lookup against the
    train matrix on every eval — but the test triples and J^K are fixed
    for the whole fit, so it is the same work re-done every epoch (the
    `ScheduledData` trick applied to the eval loop).  `rmse_cached` then
    reduces per-epoch eval to plain slices."""

    nb: jax.Array    # [T, K] int32 — J^K[test cols]
    rnb: jax.Array   # [T, K] float32 — r_{i, nb} from the *train* matrix
    expl: jax.Array  # [T, K] float32


def build_eval_cache(sp_train: SparseMatrix, JK: jax.Array, rows, cols, *,
                     mf_only: bool = False, chunk: int = 65536) -> EvalCache:
    """One lookup sweep over the test triples → EvalCache."""
    if mf_only:   # MF never reads neighbour slots — zero-width planes
        z = jnp.zeros((rows.shape[0], 0), jnp.float32)
        return EvalCache(z.astype(jnp.int32), z, z)
    nb_parts, rnb_parts, expl_parts = [], [], []
    for c0 in range(0, int(rows.shape[0]), chunk):
        i = rows[c0:c0 + chunk]
        nb = JK[cols[c0:c0 + chunk]]
        rnb, hit = lookup(sp_train, jnp.broadcast_to(i[:, None], nb.shape), nb)
        nb_parts.append(nb)
        rnb_parts.append(rnb)
        expl_parts.append(hit.astype(jnp.float32))
    z = jnp.zeros((0, JK.shape[1]), jnp.float32)
    cat = lambda ps, zz: jnp.concatenate(ps) if ps else zz
    return EvalCache(cat(nb_parts, z.astype(jnp.int32)),
                     cat(rnb_parts, z), cat(expl_parts, z))


@partial(jax.jit, static_argnames=("batch", "mf_only"))
def rmse_cached(p: Params, ec: EvalCache, rows, cols, vals, *,
                batch: int = 8192, mf_only: bool = False):
    """Test RMSE (Eq. 6) from the per-fit `EvalCache` — per-epoch eval is
    a scan of plain slices, no binary search."""
    n = rows.shape[0]
    nb_batches = max(1, -(-n // batch))
    pad = nb_batches * batch - n
    padv = lambda a: jnp.concatenate(
        [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
    rows_p, cols_p, vals_p = padv(rows), padv(cols), padv(vals)
    nb_p, rnb_p, expl_p = padv(ec.nb), padv(ec.rnb), padv(ec.expl)
    valid = (jnp.arange(nb_batches * batch) < n).astype(jnp.float32)

    def body(carry, s):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, s, batch, axis=0)
        expl = sl(expl_p)
        r = sl(vals_p)
        v = sl(valid)
        bt = Batch(sl(rows_p), sl(cols_p), r, sl(nb_p), sl(rnb_p),
                   expl, 1.0 - expl, v)
        pred = predict_mf(p, bt) if mf_only else predict(p, bt)[0]
        return carry + jnp.sum((r - pred) ** 2 * v), None

    sse, _ = jax.lax.scan(body, 0.0, jnp.arange(nb_batches) * batch)
    return jnp.sqrt(sse / n)


@partial(jax.jit, static_argnames=("batch", "mf_only"))
def rmse(p: Params, sp_train: SparseMatrix, JK, rows, cols, vals, *,
         batch: int = 8192, mf_only: bool = False):
    """Test RMSE (Eq. 6).  Neighbour ratings come from the *train* matrix."""
    n = rows.shape[0]
    nb_batches = -(-n // batch)
    pad = nb_batches * batch - n
    rows_p = jnp.concatenate([rows, rows[:1].repeat(pad)])
    cols_p = jnp.concatenate([cols, cols[:1].repeat(pad)])
    vals_p = jnp.concatenate([vals, vals[:1].repeat(pad)])
    valid = (jnp.arange(nb_batches * batch) < n).astype(jnp.float32)

    def body(carry, s):
        i = jax.lax.dynamic_slice_in_dim(rows_p, s, batch)
        j = jax.lax.dynamic_slice_in_dim(cols_p, s, batch)
        r = jax.lax.dynamic_slice_in_dim(vals_p, s, batch)
        v = jax.lax.dynamic_slice_in_dim(valid, s, batch)
        nb = JK[j]
        rnb, hit = lookup(sp_train, jnp.broadcast_to(i[:, None], nb.shape), nb)
        expl = hit.astype(jnp.float32)
        bt = Batch(i, j, r, nb, rnb, expl, 1.0 - expl, v)
        pred = predict_mf(p, bt) if mf_only else predict(p, bt)[0]
        err = (r - pred) ** 2 * v
        return carry + jnp.sum(err), None

    sse, _ = jax.lax.scan(body, 0.0, jnp.arange(nb_batches) * batch)
    return jnp.sqrt(sse / n)
