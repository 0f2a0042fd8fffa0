"""jit'd wrappers: gather plane rows → fused kernel step → scatter deltas.

The packed-parameter layout (`model.PackedParams`) makes the whole step
**two** gather/scatter pairs: one [B, F+1] row-plane gather + delta
scatter (U and b together) and one [B, F+2K+1] col-plane pair (V, W, C
and b̂) — versus the six of the pre-packed layout.  The conflict-free
batch guarantee (see `data.sparse.conflict_free_schedule`) makes the
scatter race-free: each valid i/j appears once, so adding the per-row
*delta* is exactly Eq. (5).  Deltas (not `.set`) also make padding slots
— which repeat a live triple with ``valid`` False — harmless no-ops.

The CULSH step also reads b̂ at each sample's K neighbours, items the
batch doesn't own.  On a TPU an XLA gather does that one element at a
time (≈ 8 ns each on a v5e, most of an epoch's device time), so
`neighbour_baselines` does it as an exact one-hot lookup on the MXU, up
to the catalog size where the gather is faster again.

``impl="auto"`` resolves to the pure-jnp ref on CPU (where Pallas only has
the slow interpreter) and the fused Pallas kernel elsewhere, mirroring
`kernels.candidate_score`.  This is the training hot path behind
`FitConfig.use_kernels` (via `sgd.train_epoch_scheduled`).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.model import Batch, PackedParams
from repro.kernels.mf_sgd.kernel import culsh_sgd_step, mf_sgd_step
from repro.kernels.mf_sgd.ref import culsh_sgd_step_ref, mf_sgd_step_ref


def resolve_impl(impl: str) -> str:
    """'auto' → 'ref' on CPU, 'pallas' on accelerators (call outside jit)."""
    if impl != "auto":
        return impl
    return "ref" if jax.default_backend() == "cpu" else "pallas"


# The crossover between the two lookups, measured on a TPU v5e
# (`benchmarks/bench_nb_bias.py`, PERF.md): a one-hot lookup costs about
# as much as one gathered element per 2^17 catalog items, and each call
# also makes one pass over b̂ worth about 1024 lookups; the gather costs
# one element a lookup whatever the catalog.
ONEHOT_ITEMS_PER_LOOKUP = 1 << 17
ONEHOT_TABLE_LOOKUPS = 1024


def nb_bias_vectorised(N: int, n: int) -> bool:
    """Whether `neighbour_baselines` looks ``n`` ids up in an ``N``-item
    b̂ one-hot: where that is the faster of the two, decided from the
    static shapes alone (N ≲ 123k at n = 16384, ≲ 87k at n = 2048)."""
    return N * (n + ONEHOT_TABLE_LOOKUPS) <= ONEHOT_ITEMS_PER_LOOKUP * n


def _nb_bias_onehot(bh: jax.Array, nb: jax.Array) -> jax.Array:
    """``bh[nb]`` bit for bit, as two small matmuls and a lane select.

    b̂ is laid out ``[H, 128]`` and split into its four bytes, each an
    integer 0–255 that bf16 holds exactly.  A one-hot of ``id // 128``
    against the byte planes (bytes 1 and 3 scaled by 256, also exact)
    gives, with f32 accumulation, the two 16-bit halves of every lane of
    the id's row — integers below 2^16, so exact — and an iota compare
    keeps lane ``id % 128``.  No rounding anywhere: the bits are b̂'s."""
    N = bh.shape[0]
    H = -(-N // 128)
    bits = lax.bitcast_convert_type(jnp.pad(bh, (0, H * 128 - N)),
                                    jnp.int32).reshape(H, 128)
    byte = lambda k: ((bits >> (8 * k)) & 0xFF).astype(jnp.float32)
    planes = jnp.concatenate(                            # [2H, 256]
        [jnp.concatenate([byte(0), byte(2)], 1),
         jnp.concatenate([byte(1), byte(3)], 1) * 256.0], 0)
    ids = nb.reshape(-1)
    hi, lo = ids >> 7, ids & 127
    onehot = (hi[:, None] == jnp.arange(H)[None, :]).astype(jnp.bfloat16)
    rows = jnp.dot(jnp.concatenate([onehot, onehot], 1),
                   planes.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)   # [n, 256]
    pick = lo[:, None] == jnp.arange(128)[None, :]
    half = lambda h: jnp.sum(jnp.where(pick, h, 0.0), 1).astype(jnp.int32)
    out = half(rows[:, :128]) | (half(rows[:, 128:]) << 16)
    return lax.bitcast_convert_type(out, jnp.float32).reshape(nb.shape)


def _nb_bias_gather(bh: jax.Array, nb: jax.Array) -> jax.Array:
    return bh[nb]


def neighbour_baselines(bh: jax.Array, nb: jax.Array) -> jax.Array:
    """b̂[J^K[j]]: the baselines ``bh [N]`` at the neighbour ids ``nb``
    (any shape, values in ``[0, N)``), exactly.  A TPU gather reads one
    element at a time; where `nb_bias_vectorised` says so the one-hot
    lookup does the same read on the MXU instead.  The crossover is a TPU
    measurement: compiled for a CPU, whose gather is the faster of the
    two at every size, the lookup stays a gather."""
    with jax.named_scope("nb_bias"):
        if not nb_bias_vectorised(bh.shape[0], nb.size):
            return _nb_bias_gather(bh, nb)
        return lax.platform_dependent(bh, nb, cpu=_nb_bias_gather,
                                      default=_nb_bias_onehot)


def apply_mf_sgd(pp: PackedParams, bt: Batch, hp, decay, *,
                 interpret: bool, impl: str = "pallas", tile_b: int = 256,
                 bce: bool = False) -> PackedParams:
    """CUSGD++ step applied to the packed planes via a conflict-free batch
    (only the U/V columns are touched)."""
    F = pp.F
    with jax.named_scope("gather"):
        u = pp.row[bt.i, :F]
        v = pp.col[bt.j, :F]
    args = (u, v, bt.r, bt.valid,
            jnp.float32(hp.a_u) * decay, jnp.float32(hp.a_v) * decay,
            jnp.float32(hp.l_u), jnp.float32(hp.l_v))
    with jax.named_scope("kernel"):
        if impl == "ref":
            u2, v2, _ = mf_sgd_step_ref(*args, bce=bce)
        else:
            u2, v2, _ = mf_sgd_step(*args, tile_b=tile_b,
                                    interpret=interpret, bce=bce)
    with jax.named_scope("scatter"):
        return dataclasses.replace(
            pp, row=pp.row.at[bt.i, :F].add(u2 - u),
            col=pp.col.at[bt.j, :F].add(v2 - v))


def apply_culsh_sgd(pp: PackedParams, bt: Batch, hp, decay, *,
                    interpret: bool, impl: str = "pallas", tile_b: int = 256,
                    bce: bool = False) -> PackedParams:
    """Fused six-parameter CULSH-MF step applied to the packed planes.

    XLA-level gathers assemble the plane tiles (same split as
    `candidate_score`: gathers outside, dense tiles inside the kernel).
    The neighbour baselines b̂[J^K[j]] read b̂ of items the batch doesn't
    own: `neighbour_baselines` looks them up in the pre-step b̂ column,
    one-hot on the MXU rather than one element at a time.
    """
    F, K = pp.F, pp.K
    # the kernel takes batch-minor tiles: the transposes of the [B, K]
    # batch planes cancel the ones `model.slice_batch` makes, so the
    # schedule's [K, P] planes reach the kernel without a re-layout
    with jax.named_scope("gather"):
        row = pp.row[bt.i].T                # [F+1, B]
        col = pp.col[bt.j].T                # [F+2K+1, B]
        nb = bt.nb.T                        # [K, B]
        bh_nb = neighbour_baselines(pp.bh, nb)
    d = decay
    hpv = jnp.stack([hp.a_b * d, hp.a_bh * d, hp.a_u * d, hp.a_v * d,
                     hp.a_w * d, hp.a_c * d,
                     jnp.float32(hp.l_b), jnp.float32(hp.l_bh),
                     jnp.float32(hp.l_u), jnp.float32(hp.l_v),
                     jnp.float32(hp.l_w), jnp.float32(hp.l_c), pp.mu])
    step = (culsh_sgd_step_ref if impl == "ref"
            else partial(culsh_sgd_step, tile_b=tile_b, interpret=interpret))
    with jax.named_scope("kernel"):
        row2, col2 = step(row, col, bt.rnb.T, bh_nb, bt.expl.T, bt.r,
                          bt.valid, hpv, bce=bce)
    with jax.named_scope("scatter"):
        return dataclasses.replace(
            pp, row=pp.row.at[bt.i].add((row2 - row).T),
            col=pp.col.at[bt.j].add((col2 - col).T))
