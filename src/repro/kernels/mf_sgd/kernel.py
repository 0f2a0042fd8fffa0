"""Pallas TPU kernels: fused SGD steps — CUSGD++ (`mf_sgd_step`, paper
Alg. 2) and the six-parameter CULSH-MF step (`culsh_sgd_step`, Alg. 3),
both over conflict-free batch tiles (update rule Eq. 5).

For a conflict-free batch tile (each i / j at most once — the invariant the
paper's D×D blocking provides), one VMEM pass computes

    e   = r − u·v
    u' = u + γu (e·v − λu·u)
    v' = v + γv (e·u − λv·v)

using the *pre-update* u in the v update exactly like the register-resident
CUDA kernel (both updates read the same stale operands).  This is the TPU
image of "keep u_i in registers, fuse dot + update": tile-resident operands,
one round trip to HBM per row.

The CULSH kernel works on the **packed planes** (`model.PackedParams`),
carried *batch-minor*: one sample per lane, so its tiles are
``row [F+1, TB]`` = U‖b, ``col [F+2K+1, TB]`` = V‖W‖C‖b̂ and the three
neighbour planes ``[K, TB]``.  The pallas_call carries 7 operands and 2
outputs instead of the 15/6 of the pre-packed layout, the surrounding step
is one gather + one delta-scatter per plane plus the lookup of the
neighbours' b̂ (`ops.neighbour_baselines`), and in-kernel the planes are
split with static sublane slices.  Batch-minor is what the TPU needs here:
the schedule-ordered neighbour planes (`model.ScheduledData`) are stored
``[K, P]``, which tiles without padding, and a kernel operand ``[B, K]``
would force XLA to re-lay the whole ``[P, K]`` plane out at 128 lanes —
four times its size for K=32, more than the chip holds at 9.9M ratings.
The per-sample scalars (r, valid, b, b̂, e) are ``[1, TB]`` lane rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the hyper-parameter vector is read as scalars: it lives in SMEM whole
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _sgd_kernel(bce, u_ref, v_ref, r_ref, valid_ref, hp_ref,
                u_out, v_out, e_out):
    u = u_ref[...]                       # [TB, F]
    v = v_ref[...]
    r = r_ref[...]                       # [TB, 1]
    valid = valid_ref[...]
    gu, gv, lu, lv = hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3]
    pred = jnp.sum(u * v, axis=-1, keepdims=True)
    e = (r - (jax.nn.sigmoid(pred) if bce else pred)) * valid
    u_out[...] = u + gu * (e * v - lu * u) * valid
    v_out[...] = v + gv * (e * u - lv * v) * valid
    e_out[...] = e


def _culsh_kernel(bce, row_ref, col_ref, rnb_ref, bhnb_ref, expl_ref,
                  r_ref, valid_ref, hp_ref, row_out, col_out):
    row = row_ref[...]                         # [F+1, TB] — U ‖ b
    col = col_ref[...]                         # [F+2K+1, TB] — V ‖ W ‖ C ‖ b̂
    rnb = rnb_ref[...]                         # [K, TB]
    bh_nb = bhnb_ref[...]                      # [K, TB] — b̂[J^K[j]]
    expl = expl_ref[...]
    r, valid = r_ref[...], valid_ref[...]      # [1, TB]
    F = row.shape[0] - 1
    K = rnb.shape[0]
    gb, gbh, gu, gv = hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3]
    gw, gc = hp_ref[4], hp_ref[5]
    lb, lbh, lu, lv = hp_ref[6], hp_ref[7], hp_ref[8], hp_ref[9]
    lw, lc = hp_ref[10], hp_ref[11]
    mu = hp_ref[12]

    u, b = row[:F], row[F:F + 1]
    v, w = col[:F], col[F:F + K]
    c, bh = col[F + K:F + 2 * K], col[F + 2 * K:F + 2 * K + 1]
    impl = 1.0 - expl
    bbar = mu + b + bh
    resid = (rnb - (mu + b + bh_nb)) * expl
    nR = jnp.sum(expl, axis=0, keepdims=True)
    nN = jnp.sum(impl, axis=0, keepdims=True)
    sR = jnp.where(nR > 0, jax.lax.rsqrt(jnp.maximum(nR, 1.0)), 0.0)
    sN = jnp.where(nN > 0, jax.lax.rsqrt(jnp.maximum(nN, 1.0)), 0.0)
    pred = (bbar + sR * jnp.sum(resid * w, axis=0, keepdims=True)
            + sN * jnp.sum(impl * c, axis=0, keepdims=True)
            + jnp.sum(u * v, axis=0, keepdims=True))
    e = (r - (jax.nn.sigmoid(pred) if bce else pred)) * valid
    row_out[:F] = u + gu * (e * v - lu * u) * valid
    row_out[F:F + 1] = b + gb * (e - lb * b) * valid
    col_out[:F] = v + gv * (e * u - lv * v) * valid
    col_out[F:F + K] = w + gw * (sR * e * resid - lw * w) * expl * valid
    col_out[F + K:F + 2 * K] = c + gc * (sN * e - lc * c) * impl * valid
    col_out[F + 2 * K:F + 2 * K + 1] = bh + gbh * (e - lbh * bh) * valid


def _clamp_tile(tile_b: int, B: int) -> int:
    """Width-generic tiling: narrow schedule tiers (quarter/eighth width)
    shouldn't pay for a mostly-padding 256-row tile.  Clamp the tile to
    the batch rounded up to the fp32 sublane multiple (8)."""
    return max(8, min(tile_b, -(-B // 8) * 8))


def _lane_tile(tile_b: int, B: int) -> int:
    """Batch-minor tiling: a batch that fits one tile is one whole-width
    block (any width — a block may span its array's full dim); wider
    batches split into 128-lane multiples."""
    return B if B <= tile_b else -(-tile_b // 128) * 128


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret", "bce"))
def culsh_sgd_step(row, col, rnb, bh_nb, expl, r, valid, hp, *,
                   interpret: bool, tile_b: int = 256, bce: bool = False):
    """Fused six-parameter CULSH-MF step (paper Alg. 3, update rule Eq. 5)
    on batch-minor packed plane tiles.

    One VMEM pass per batch tile computes the Eq. (1) forward *and* both
    updated parameter planes — the TPU image of the paper's register-
    resident CUDA kernel, which the load-balance property of §4.2(2)
    (every sample touches exactly K of the 2K {w, c} slots) keeps dense.
    Batch must be conflict-free but may have any width (every schedule
    tier routes through here).  Operand layout and the ``hp`` 13-vector
    are documented on `ref.culsh_sgd_step_ref`; plane gathers/scatters
    happen in `ops`.
    """
    B = row.shape[1]
    F = row.shape[0] - 1
    K = rnb.shape[0]
    tile = _lane_tile(tile_b, B)
    r, valid = r[None], valid.astype(jnp.float32)[None]
    ops = (row, col, rnb, bh_nb, expl, r, valid)
    pad = (-B) % tile
    if pad:
        ops = tuple(jnp.pad(a, ((0, 0), (0, pad))) for a in ops)
    Bp = B + pad
    blk = lambda d: pl.BlockSpec((d, tile), lambda i: (0, i))
    outs = pl.pallas_call(
        functools.partial(_culsh_kernel, bce),
        grid=(Bp // tile,),
        in_specs=[blk(F + 1), blk(F + 2 * K + 1), blk(K), blk(K), blk(K),
                  blk(1), blk(1), _SMEM],
        out_specs=[blk(F + 1), blk(F + 2 * K + 1)],
        out_shape=[jax.ShapeDtypeStruct((F + 1, Bp), jnp.float32),
                   jax.ShapeDtypeStruct((F + 2 * K + 1, Bp), jnp.float32)],
        interpret=interpret,
    )(*ops, hp.astype(jnp.float32))
    return tuple(o[:, :B] for o in outs)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret", "bce"))
def mf_sgd_step(u, v, r, valid, gamma_u, gamma_v, lam_u, lam_v, *,
                interpret: bool, tile_b: int = 256, bce: bool = False):
    """u,v [B,F]; r,valid [B] → (u', v', e).  Batch must be conflict-free;
    any width (tile clamped to the batch — see `_clamp_tile`)."""
    B, F = u.shape
    tile_b = _clamp_tile(tile_b, B)
    pad = (-B) % tile_b
    if pad:
        u = jnp.pad(u, ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0)))
        r = jnp.pad(r, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    Bp = u.shape[0]
    hp = jnp.stack([gamma_u, gamma_v, lam_u, lam_v]).astype(jnp.float32)

    mat = pl.BlockSpec((tile_b, F), lambda i: (i, 0))
    col = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    u2, v2, e = pl.pallas_call(
        functools.partial(_sgd_kernel, bce),
        grid=(Bp // tile_b,),
        in_specs=[mat, mat, col, col, _SMEM],
        out_specs=[mat, mat, col],
        out_shape=[jax.ShapeDtypeStruct((Bp, F), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, F), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.float32)],
        interpret=interpret,
    )(u, v, r[:, None], valid.astype(jnp.float32)[:, None], hp)
    return u2[:B], v2[:B], e[:B, 0]
