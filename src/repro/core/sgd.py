"""Stochastic optimization — paper Eq. (4)/(5) updates + Eq. (7) dynamic LR.

Two engines, mirroring the paper's two contributions:

* ``mf_step``        — CUSGD++ analogue: plain MF {U, V} only.
* ``culsh_step``     — CULSH-MF: the full six-parameter fused update.

Both exist in two layouts.  The *unpacked* steps above take `model.Params`
and scatter each parameter separately — they are the reference semantics
and the engine of the general path (`train_epoch`, the online Alg.-4
building block).  The *packed* steps (``mf_step_packed`` /
``culsh_step_packed``) take `model.PackedParams` — row-side parameters in
one [M, F+1] plane, col-side in one [N, F+2K+1] plane — and emit **two**
gather/scatter pairs per step instead of six; they are bit-identical to
the unpacked steps (shared forward + shared delta computation) and power
the scheduled hot path.

TPU adaptation (DESIGN.md §2/§8.1): updates are applied to a *mini-batch*
with scatter-add (`.at[].add`).  When the batch is conflict-free (each i and
each j at most once — the invariant the paper's D×D blocking provides) this
is *exactly* Eq. (5) applied in parallel; with collisions it is the summed
batch-SGD step.  Both engines are pure functions scanned over an epoch.

Two epoch drivers:

* ``train_epoch``            — general case: binary-search batch assembly +
  collision rescaling every batch (also the Alg.-4 online building block).
  Unpacked `Params` in, unpacked out.
* ``train_epoch_scheduled``  — offline hot path: contiguous-slice assembly
  from the schedule-ordered `ScheduledData`, width-tiered conflict-free
  scans over packed planes (+ optional fused Pallas kernels), an optional
  shard_map block-rotation tier over the dense `ShardData` cells,
  precomputed leftover collision scales, params donated across epochs.
  `PackedParams` in, `PackedParams` out.  See bench_train.py.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.model import (Batch, PackedParams, Params, ScheduledData,
                              ShardData, assemble, predict, predict_gathered,
                              predict_mf, slice_batch)
from repro.data.sparse import EpochSchedule, SparseMatrix, epoch_batches
from repro.kernels.mf_sgd.ops import (apply_culsh_sgd, apply_mf_sgd,
                                      nb_bias_vectorised,
                                      neighbour_baselines)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Hyper:
    """Eq. (5) step sizes α, regularisation λ and the Eq. (7) decay β,
    as stated for ratings on a 1–5 scale.

    `trainer.fit` maps them to the scale of its training ratings
    (`rating_scale`, `for_rating_scale`): with s the smallest power of
    two ≥ (max − min) / 4 of the ratings, at least 1, it trains with
    a_u, a_v ÷ s and l_u, l_v × s, a_w ÷ s² and l_w × s², every other
    step, λ and β as stated.  Eq. (5) on the ratings r then follows the
    path of Eq. (5) on r / s under the stated steps, its parameters
    mapped as U, V × √s; μ, b, b̂, C × s; W unchanged: the prediction
    and the error scale by s, so the b, b̂ and C steps (linear in e and
    in their own parameter) need nothing, the U, V steps (e·V scales by
    s√s) need α ÷ s, the W step (e·residual scales by s²) needs α ÷ s²,
    and each λ rises as its α falls, so every α·λ is unchanged.  A power
    of two keeps the mapped steps exact in float, and 1–5 data (s = 1)
    trains with the stated steps bit for bit.  The online updater and
    the loop use the steps they are given, unmapped.
    """
    # initial learning rates (paper Table 3/5 names)
    a_b: float = 0.02
    a_bh: float = 0.02
    a_u: float = 0.02
    a_v: float = 0.02
    a_w: float = 0.001
    a_c: float = 0.001
    # regularization
    l_b: float = 0.01
    l_bh: float = 0.01
    l_u: float = 0.01
    l_v: float = 0.01
    l_w: float = 0.05
    l_c: float = 0.05
    # Eq. (7) decay
    beta: float = 0.3

    def for_rating_scale(self, s: int) -> "Hyper":
        """The steps for ratings ``s`` times the stated scale (see the
        class docstring); ``s`` a power of two, so the result is exact."""
        if s == 1:
            return self
        return dataclasses.replace(
            self, a_u=self.a_u / s, a_v=self.a_v / s, l_u=self.l_u * s,
            l_v=self.l_v * s, a_w=self.a_w / s ** 2, l_w=self.l_w * s ** 2)


def rating_scale(vals) -> int:
    """s: the smallest power of two ≥ (max − min) / 4 of ``vals``, at
    least 1 (1 on any 1–5 data, 32 on 0–100)."""
    vals = jnp.asarray(vals)
    span = float(jnp.max(vals) - jnp.min(vals)) / 4 if vals.size else 0.0
    s = 1
    while s < span:
        s *= 2
    return s


def lr_decay(hp: Hyper, t: jax.Array) -> jax.Array:
    """γ_t = α / (1 + β·t^1.5) — Eq. (7); returns the *decay factor*."""
    return 1.0 / (1.0 + hp.beta * jnp.power(t.astype(jnp.float32), 1.5))


def _batch_scales(M: int, N: int, bt: Batch, conflict_free: bool, scales):
    """(si, sj, si_col, sj_col) — collision normalizers and their [B, 1]
    broadcasts, so rows hit k× in a batch get the *mean* update (zipf
    heads would otherwise receive k summed steps and diverge).

    ``conflict_free`` (a static promise that each i and j appears at most
    once, the D×D-block invariant) elides the two O(M)+O(N) scatter-add
    allocations entirely: all counts are 1.  ``scales`` optionally
    supplies host-precomputed (si, sj) — the scheduled leftover batches
    have fixed composition per fit, so their counts are schedule
    constants (`EpochSchedule.lo_scale_*`), not per-batch work."""
    if scales is not None:
        si, sj = scales
        return si, sj, si[:, None], sj[:, None]
    if conflict_free:
        one = jnp.ones((), jnp.float32)
        return one, one, one, one
    ci = jnp.zeros((M,), jnp.float32).at[bt.i].add(bt.valid)
    cj = jnp.zeros((N,), jnp.float32).at[bt.j].add(bt.valid)
    si = 1.0 / jnp.maximum(ci[bt.i], 1.0)
    sj = 1.0 / jnp.maximum(cj[bt.j], 1.0)
    return si, sj, si[:, None], sj[:, None]


def _error(r, pred, bce: bool):
    """e_ij: residual (L2) or r − σ(pred) (BCE — the paper's implicit-
    feedback variant: "we change the loss function ... to cross entropy,
    and the update formula will follow the corresponding change")."""
    return r - (jax.nn.sigmoid(pred) if bce else pred)


def _mf_deltas(bt: Batch, e, ui, vj, hp: Hyper, decay, si_c, sj_c):
    """(du, dv) for the CUSGD++ update — shared by both layouts."""
    gu = hp.a_u * decay
    gv = hp.a_v * decay
    vmask = bt.valid[:, None]
    du = gu * (e[:, None] * vj - hp.l_u * ui) * vmask * si_c
    dv = gv * (e[:, None] * ui - hp.l_v * vj) * vmask * sj_c
    return du, dv


def _culsh_deltas(bt: Batch, e, aux, b_i, bh_j, ui, vj, wj, cj, hp: Hyper,
                  decay, si, sj, si_c, sj_c):
    """The six Eq. (5) parameter deltas from row-aligned gathered operands
    — shared by the unpacked and packed steps so the two layouts are
    bit-identical by construction."""
    d = decay
    vmask = bt.valid[:, None]
    db = hp.a_b * d * (e - hp.l_b * b_i) * bt.valid * si
    dbh = hp.a_bh * d * (e - hp.l_bh * bh_j) * bt.valid * sj
    du = hp.a_u * d * (e[:, None] * vj - hp.l_u * ui) * vmask * si_c
    dv = hp.a_v * d * (e[:, None] * ui - hp.l_v * vj) * vmask * sj_c
    # w_{j,k} ← w + γw(|R|^{-1/2}·e·(r_nb − b̄_nb) − λw·w) on explicit slots
    dw = (aux["sR"][:, None] * e[:, None] * aux["resid"] - hp.l_w * wj) * bt.expl
    dc = (aux["sN"][:, None] * e[:, None] - hp.l_c * cj) * bt.impl
    dw = hp.a_w * d * dw * vmask * sj_c
    dc = hp.a_c * d * dc * vmask * sj_c
    return db, dbh, du, dv, dw, dc


def mf_step(p: Params, bt: Batch, hp: Hyper, decay, bce: bool = False,
            conflict_free: bool = False) -> Params:
    """CUSGD++: u_i ← u_i + γ(e·v_j − λu·u_i);  v symmetric.  Unpacked
    reference layout — the hot path is `mf_step_packed`."""
    e = _error(bt.r, predict_mf(p, bt), bce) * bt.valid
    ui, vj = p.U[bt.i], p.V[bt.j]
    _, _, si_c, sj_c = _batch_scales(p.U.shape[0], p.V.shape[0], bt,
                                     conflict_free, None)
    du, dv = _mf_deltas(bt, e, ui, vj, hp, decay, si_c, sj_c)
    return dataclasses.replace(p, U=p.U.at[bt.i].add(du),
                               V=p.V.at[bt.j].add(dv))


def mf_step_packed(pp: PackedParams, bt: Batch, hp: Hyper, decay,
                   bce: bool = False, conflict_free: bool = False,
                   scales=None) -> PackedParams:
    """CUSGD++ on the packed planes: one gather + one scatter per side,
    touching only the U/V columns.  Bit-identical to `mf_step` (same
    delta computation on the same gathered values)."""
    F = pp.F
    with jax.named_scope("gather"):
        ui = pp.row[bt.i, :F]
        vj = pp.col[bt.j, :F]
    e = _error(bt.r, jnp.sum(ui * vj, 1), bce) * bt.valid
    _, _, si_c, sj_c = _batch_scales(pp.row.shape[0], pp.col.shape[0], bt,
                                     conflict_free, scales)
    du, dv = _mf_deltas(bt, e, ui, vj, hp, decay, si_c, sj_c)
    with jax.named_scope("scatter"):
        return dataclasses.replace(pp, row=pp.row.at[bt.i, :F].add(du),
                                   col=pp.col.at[bt.j, :F].add(dv))


def culsh_step(p: Params, bt: Batch, hp: Hyper, decay,
               bce: bool = False, conflict_free: bool = False,
               bh_nb: jax.Array | None = None) -> Params:
    """CULSH-MF: the fused Eq. (5) update of {b, b̂, U, V, W, C}.

    Unpacked reference layout (six scatters) — the scheduled hot path is
    `culsh_step_packed`, which shares this function's forward and delta
    computation and must stay bit-identical to it (tested).

    With ``conflict_free`` (static) the batch is promised to touch each i
    and each j at most once (the D×D-block invariant), making the summed
    scatter exactly the parallel Eq. (5) with no rescaling.  ``bh_nb``
    optionally substitutes pre-gathered neighbour baselines (see
    `model.predict` — the shard-tier stale-read)."""
    pred, aux = predict(p, bt, bh_nb=bh_nb)
    e = _error(bt.r, pred, bce) * bt.valid
    si, sj, si_c, sj_c = _batch_scales(p.U.shape[0], p.V.shape[0], bt,
                                       conflict_free, None)
    db, dbh, du, dv, dw, dc = _culsh_deltas(
        bt, e, aux, p.b[bt.i], p.bh[bt.j], p.U[bt.i], p.V[bt.j],
        p.W[bt.j], p.C[bt.j], hp, decay, si, sj, si_c, sj_c)
    return dataclasses.replace(
        p, b=p.b.at[bt.i].add(db), bh=p.bh.at[bt.j].add(dbh),
        U=p.U.at[bt.i].add(du), V=p.V.at[bt.j].add(dv),
        W=p.W.at[bt.j].add(dw), C=p.C.at[bt.j].add(dc))


def culsh_step_packed(pp: PackedParams, bt: Batch, hp: Hyper, decay,
                      bce: bool = False, conflict_free: bool = False,
                      bh_nb: jax.Array | None = None,
                      scales=None) -> PackedParams:
    """CULSH-MF on the packed planes: the six scatters of `culsh_step`
    become one [B, F+1] row-plane scatter and one [B, F+2K+1] col-plane
    scatter (the per-sample payload is identical — packing only fuses the
    ops).  Bit-identical to `culsh_step` by shared-helper construction.

    ``scales`` optionally supplies the precomputed (si, sj) collision
    normalizers (`EpochSchedule.lo_scale_*`) for the scheduled leftover
    batches; ``bh_nb`` is the shard-tier epoch-start b̂ snapshot gather."""
    F, K = pp.F, pp.K
    with jax.named_scope("gather"):
        row = pp.row[bt.i]                                 # [B, F+1]
        col = pp.col[bt.j]                                 # [B, F+2K+1]
        bh_of_nb = (neighbour_baselines(pp.bh, bt.nb) if bh_nb is None
                    else bh_nb)
    ui, b_i = row[:, :F], row[:, F]
    vj, wj = col[:, :F], col[:, F:F + K]
    cj, bh_j = col[:, F + K:F + 2 * K], col[:, F + 2 * K]
    pred, aux = predict_gathered(pp.mu, b_i, bh_j, ui, vj, wj, cj,
                                 bh_of_nb, bt.rnb, bt.expl, bt.impl)
    e = _error(bt.r, pred, bce) * bt.valid
    si, sj, si_c, sj_c = _batch_scales(pp.row.shape[0], pp.col.shape[0], bt,
                                       conflict_free, scales)
    db, dbh, du, dv, dw, dc = _culsh_deltas(
        bt, e, aux, b_i, bh_j, ui, vj, wj, cj, hp, decay, si, sj, si_c, sj_c)
    with jax.named_scope("scatter"):
        return dataclasses.replace(
            pp,
            row=pp.row.at[bt.i].add(jnp.concatenate([du, db[:, None]],
                                                    axis=1)),
            col=pp.col.at[bt.j].add(
                jnp.concatenate([dv, dw, dc, dbh[:, None]], axis=1)))


@partial(jax.jit, static_argnames=("batch", "mf_only", "bce"),
         donate_argnames=("p",))
def train_epoch(p: Params, sp: SparseMatrix, JK: jax.Array, key: jax.Array,
                epoch: jax.Array, hp: Hyper, *, batch: int = 4096,
                mf_only: bool = False, bce: bool = False) -> Params:
    """One epoch: shuffled mini-batches scanned with the fused step.

    The general-case engine: per-batch binary-search assembly and collision
    rescaling, correct for any batching.  Offline fits should prefer
    `train_epoch_scheduled`, which precomputes both.  ``p`` is donated —
    U/V/… update in place across epochs instead of ping-ponging buffers.
    """
    idx, valid = epoch_batches(key, sp.nnz, batch)
    decay = lr_decay(hp, epoch)

    def body(pp, ib):
        bidx, bvalid = ib
        bt = assemble(sp, JK, bidx, bvalid)
        pp = (mf_step(pp, bt, hp, decay, bce) if mf_only
              else culsh_step(pp, bt, hp, decay, bce))
        return pp, None

    p, _ = jax.lax.scan(body, p, (idx, valid))
    return p


def _cf_scan(pp: PackedParams, sd: ScheduledData, starts, valid, hp, decay, *,
             width: int, mf_only: bool, bce: bool, conflict_free: bool,
             use_kernels: bool, impl: str, interpret: bool, tile_b: int,
             scales=None) -> PackedParams:
    """Scan one schedule tier: contiguous window assembly + packed step.
    ``scales`` carries the per-batch precomputed collision normalizers
    for the leftover tier."""

    valid = valid.astype(jnp.float32)   # once per tier, not per scan step
    xs = ((starts, valid) if scales is None
          else (starts, valid, scales[0], scales[1]))

    def body(p_, sv):
        if scales is None:
            s, val = sv
            sc = None
        else:
            s, val, si, sj = sv
            sc = (si, sj)
        bt = slice_batch(sd, s, width, val)
        if use_kernels and conflict_free:
            if mf_only:
                p_ = apply_mf_sgd(p_, bt, hp, decay, impl=impl,
                                  tile_b=tile_b, interpret=interpret, bce=bce)
            else:
                p_ = apply_culsh_sgd(p_, bt, hp, decay, impl=impl,
                                     tile_b=tile_b, interpret=interpret,
                                     bce=bce)
        elif mf_only:
            p_ = mf_step_packed(p_, bt, hp, decay, bce,
                                conflict_free=conflict_free, scales=sc)
        else:
            p_ = culsh_step_packed(p_, bt, hp, decay, bce,
                                   conflict_free=conflict_free, scales=sc)
        return p_, None

    pp, _ = jax.lax.scan(body, pp, xs)
    return pp


def _epoch_steps(sched: EpochSchedule):
    """(batch width, steps) of each part of one `train_epoch_scheduled`
    epoch: the width tiers, the leftovers (at the widest width) and the
    shard tier's cells."""
    parts = list(zip(sched.widths, (int(s.shape[0])
                                    for s in sched.tier_starts)))
    parts.append((sched.widths[0], int(sched.lo_starts.shape[0])))
    if sched.shard_span:
        parts.append((sched.shard_width,
                      math.prod(sched.shard_starts.shape)))
    return parts


def nb_bias_lookup_steps(sched: EpochSchedule, N: int, K: int, *,
                         mf_only: bool, platform: str) -> int:
    """Steps of one `train_epoch_scheduled` epoch over an ``N``-item col
    plane, compiled for ``platform``, whose neighbour baselines take
    `neighbour_baselines`' one-hot path, each step looking up K ids for
    every slot of its batch."""
    if mf_only or platform == "cpu":
        return 0
    return sum(n for w, n in _epoch_steps(sched)
               if nb_bias_vectorised(N, K * w))


def nb_bias_gather_steps(sched: EpochSchedule, N: int, K: int, *,
                         mf_only: bool, platform: str) -> int:
    """Steps of the same epoch whose neighbour baselines take the gather:
    every CULSH step that `nb_bias_lookup_steps` does not count."""
    if mf_only:
        return 0
    return sum(n for _, n in _epoch_steps(sched)) - nb_bias_lookup_steps(
        sched, N, K, mf_only=mf_only, platform=platform)


_SHD_FIELDS = ("i", "j", "r", "nb", "rnb", "expl")


def _shard_round_shuffle(shd: ShardData, sched: EpochSchedule, key):
    """Per-epoch round permutation for the block-aligned tier.

    Rounds are permuted *within* each sub-epoch, identically across
    devices: batches at the same (s, r) touch disjoint blocks by
    construction, so any common round order preserves both
    conflict-freedom and single-device/shard-map parity.  Returns the
    round-permuted (ShardData, valid)."""
    D, S, R = sched.shard_starts.shape
    if R == 0:
        return shd, sched.shard_valid
    perms = jax.vmap(lambda k: jax.random.permutation(k, R))(
        jax.random.split(key, S))                      # [S, R]

    def prm(a):
        idx = perms.reshape((1, S, R) + (1,) * (a.ndim - 3))
        return jnp.take_along_axis(a, idx, axis=2)

    return jax.tree.map(prm, shd), prm(sched.shard_valid)


def _cell_batch(bi, bj, br, bnb, brnb, bexpl, val) -> Batch:
    """A dense ShardData cell *is* the batch — no window slicing."""
    return Batch(i=bi, j=bj, r=br, nb=bnb, rnb=brnb, expl=bexpl,
                 impl=1.0 - bexpl, valid=val)


def _shard_replay(pp: PackedParams, shd: ShardData, valid,
                  sched: EpochSchedule, hp: Hyper, decay, *,
                  mf_only: bool, bce: bool) -> PackedParams:
    """Single-device replay of the shard tier in the identical (s, r, d)
    cell order and with the identical epoch-start b̂ snapshot — bit-equal
    to the `jax.shard_map` path (a step's D cells touch disjoint
    parameter blocks, so sequential scatter == parallel block update)."""
    D, S, R = sched.shard_starts.shape
    bh0 = None if mf_only else pp.bh
    flat = lambda a: jnp.moveaxis(a, 0, 2).reshape((S * R * D,) + a.shape[3:])
    xs = tuple(flat(getattr(shd, f)) for f in _SHD_FIELDS) + (
        flat(valid.astype(jnp.float32)),)

    def body(p_, sv):
        bt = _cell_batch(*sv)
        if mf_only:
            p_ = mf_step_packed(p_, bt, hp, decay, bce, conflict_free=True)
        else:
            p_ = culsh_step_packed(p_, bt, hp, decay, bce, conflict_free=True,
                                   bh_nb=neighbour_baselines(bh0, bt.nb))
        return p_, None

    pp, _ = jax.lax.scan(body, pp, xs)
    return pp


def _sharded_tier(pp: PackedParams, shd: ShardData, valid,
                  sched: EpochSchedule, hp: Hyper, decay, mesh, *,
                  mf_only: bool, bce: bool) -> PackedParams:
    """Run the block-aligned tier under `jax.shard_map` (cuMF rotation).

    Device ``d`` scans sub-epoch ``s``'s rounds for block ``((d+s)%D, d)``:
    the col plane (V/W/C/b̂ blocks) stays put, the row plane (U/b blocks)
    ring-rotates once per sub-epoch — a *single* `ppermute` per rotation
    now that U and b travel in one packed plane, and after D rotations
    every row block is back home so the out-specs reassemble the planes
    positionally.  The `ShardData` cells shard with the device axis
    (``P("shard")``): each device holds only its own cells' triples.
    Neighbour baselines b̂[nb] use the epoch-start snapshot ``bh0`` since
    neighbour cols cross block boundaries.  Planes must be in the
    schedule's block-padded id space (`model.remap_params`)."""
    from jax.sharding import PartitionSpec as P

    D = sched.shards
    mB, nB = sched.block_rows, sched.block_cols
    F, K = pp.F, pp.K
    bh0 = pp.bh
    blocks = lambda a, nb: a.reshape((D, nb) + a.shape[1:])

    def device_fn(rowb, colb, mu, bh0, decay, shd_d, valid_d):
        d = jax.lax.axis_index("shard")
        rowb, colb = rowb[0], colb[0]
        data = jax.tree.map(lambda a: a[0], shd_d)
        valid_d = valid_d[0].astype(jnp.float32)
        col0 = d * nB

        def make_step(row0):
            def step(carry, sv):
                rowp, colp = carry
                bt = _cell_batch(*sv)
                ok = ((bt.i >= row0) & (bt.i < row0 + mB)
                      & (bt.j >= col0) & (bt.j < col0 + nB))
                bt = dataclasses.replace(
                    bt, i=jnp.clip(bt.i - row0, 0, mB - 1),
                    j=jnp.clip(bt.j - col0, 0, nB - 1),
                    valid=bt.valid * ok)
                pl = PackedParams(row=rowp, col=colp, mu=mu, F=F, K=K)
                if mf_only:
                    pl = mf_step_packed(pl, bt, hp, decay, bce,
                                        conflict_free=True)
                else:
                    pl = culsh_step_packed(
                        pl, bt, hp, decay, bce, conflict_free=True,
                        bh_nb=neighbour_baselines(bh0, bt.nb))
                return (pl.row, pl.col), None
            return step

        ring = [(i, (i - 1) % D) for i in range(D)]
        for s in range(D):
            row0 = ((d + s) % D) * mB
            xs = tuple(getattr(data, f)[s] for f in _SHD_FIELDS) + (
                valid_d[s],)
            (rowb, colb), _ = jax.lax.scan(make_step(row0), (rowb, colb), xs)
            rowb = jax.lax.ppermute(rowb, "shard", ring)
        return rowb[None], colb[None]

    sh = P("shard")
    fn = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(sh, sh, P(), P(), P(), sh, sh),
        out_specs=(sh, sh))
    row, col = fn(blocks(pp.row, mB), blocks(pp.col, nB), pp.mu, bh0, decay,
                  shd, valid)
    unb = lambda a: a.reshape((-1,) + a.shape[2:])
    return dataclasses.replace(pp, row=unb(row), col=unb(col))


@partial(jax.jit,
         static_argnames=("mf_only", "bce", "use_kernels", "impl",
                          "interpret", "tile_b", "mesh"),
         donate_argnames=("pp",))
def train_epoch_scheduled(pp: PackedParams, sd: ScheduledData,
                          sched: EpochSchedule, key: jax.Array,
                          epoch: jax.Array, hp: Hyper, *,
                          shd: ShardData | None = None,
                          mf_only: bool = False, bce: bool = False,
                          use_kernels: bool = False, impl: str = "ref",
                          interpret: bool = False, tile_b: int = 256,
                          mesh=None) -> PackedParams:
    """One epoch over a tiered conflict-free schedule (the offline hot path).

    cuMF_SGD's conflict-free fine-grained SGD, tiered and laid out for the
    compiler:

    * parameters live in the two packed planes (`model.PackedParams`), so
      every step is two gather/scatter pairs, not six;
    * batch assembly is a contiguous `dynamic_slice` of the schedule-
      ordered `ScheduledData` — no per-batch gather or binary search;
    * the block-aligned shard tier (if the schedule has one) runs first
      over the dense `ShardData` cells (pass ``shd``) — under
      `jax.shard_map` over ``mesh`` when given (cells sharded with the
      device axis), otherwise replayed sequentially in the identical
      (s, r, d) order (exact parity: the D batches of a step touch
      disjoint parameter blocks);
    * each width tier is one `lax.scan` of exact Eq. (5) steps (static
      shapes per tier), optionally through the fused `kernels/mf_sgd`
      step (``use_kernels``; ``impl`` pre-resolved via `ops.resolve_impl`
      outside jit, tile auto-clamped to the tier width);
    * leftover batches (zipf heads) fall back to the scaled summed step
      with their collision normalizers precomputed in the schedule
      (`lo_scale_*`) — no per-batch O(M)+O(N) recount;
    * ``pp`` is donated so parameters update in place across epochs.

    Batch order is reshuffled every epoch (conflict-freedom is invariant
    under batch permutation); within-batch composition is fixed per fit.
    """
    decay = lr_decay(hp, epoch)
    keys = jax.random.split(key, 2 + len(sched.tier_starts))
    kw = dict(mf_only=mf_only, bce=bce, use_kernels=use_kernels, impl=impl,
              interpret=interpret)

    # named scopes (device-side names in a profile, no change to the
    # program): one per tier, and gather / kernel / scatter in each step
    if sched.shard_span:
        if shd is None:
            raise ValueError("schedule has a shard tier — pass "
                             "shd=model.build_shard_data(...)")
        with jax.named_scope("shard_tier"):
            shd_p, valid_p = _shard_round_shuffle(shd, sched, keys[0])
            if mesh is not None:
                pp = _sharded_tier(pp, shd_p, valid_p, sched, hp, decay,
                                   mesh, mf_only=mf_only, bce=bce)
            else:
                # same cells, same (s, r, d) order, same b̂ snapshot →
                # parity
                pp = _shard_replay(pp, shd_p, valid_p, sched, hp, decay,
                                   mf_only=mf_only, bce=bce)

    for t, (starts, valid) in enumerate(zip(sched.tier_starts,
                                            sched.tier_valid)):
        if not starts.shape[0]:
            continue
        with jax.named_scope(f"tier{t}_w{sched.widths[t]}"):
            order = jax.random.permutation(keys[2 + t], starts.shape[0])
            # tile_b passes through unclamped: the kernels fit the tile to
            # the batch themselves (`_clamp_tile`, `_lane_tile`), which a
            # min() against a non-power-of-two tier width would defeat
            pp = _cf_scan(pp, sd, starts[order], valid[order], hp, decay,
                          width=sched.widths[t], conflict_free=True,
                          tile_b=tile_b, **kw)

    if sched.lo_starts.shape[0]:
        with jax.named_scope("leftovers"):
            order = jax.random.permutation(keys[1],
                                           sched.lo_starts.shape[0])
            pp = _cf_scan(pp, sd, sched.lo_starts[order],
                          sched.lo_valid[order], hp, decay,
                          width=sched.widths[0], conflict_free=False,
                          tile_b=tile_b,
                          scales=(sched.lo_scale_i[order],
                                  sched.lo_scale_j[order]),
                          **kw | dict(use_kernels=False))
    return pp
