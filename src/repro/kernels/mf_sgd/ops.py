"""jit'd wrappers: gather plane rows → fused kernel step → scatter deltas.

The packed-parameter layout (`model.PackedParams`) makes the whole step
**two** gather/scatter pairs: one [B, F+1] row-plane gather + delta
scatter (U and b together) and one [B, F+2K+1] col-plane pair (V, W, C
and b̂) — versus the six of the pre-packed layout.  The conflict-free
batch guarantee (see `data.sparse.conflict_free_schedule`) makes the
scatter race-free: each valid i/j appears once, so adding the per-row
*delta* is exactly Eq. (5).  Deltas (not `.set`) also make padding slots
— which repeat a live triple with ``valid`` False — harmless no-ops.

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

from repro.core.model import Batch, PackedParams
from repro.kernels.mf_sgd.kernel import culsh_sgd_step, mf_sgd_step
from repro.kernels.mf_sgd.ref import culsh_sgd_step_ref, mf_sgd_step_ref


def resolve_impl(impl: str) -> str:
    """'auto' → 'ref' on CPU, 'pallas' on accelerators (call outside jit)."""
    if impl != "auto":
        return impl
    return "ref" if jax.default_backend() == "cpu" else "pallas"


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
    `candidate_score`: gathers outside, dense tiles inside the kernel);
    the only extra gather is the neighbour-baseline read b̂[J^K[j]],
    which needs rows of the col plane the batch doesn't own.
    """
    F, K = pp.F, pp.K
    # the kernel takes batch-minor tiles: the transposes of the [B, K]
    # batch planes cancel the ones `model.slice_batch` makes, so the
    # schedule's [K, P] planes reach the kernel without a re-layout
    with jax.named_scope("gather"):
        row = pp.row[bt.i].T                # [F+1, B]
        col = pp.col[bt.j].T                # [F+2K+1, B]
        nb = bt.nb.T                        # [K, B]
        bh_nb = pp.col[nb, F + 2 * K]
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
