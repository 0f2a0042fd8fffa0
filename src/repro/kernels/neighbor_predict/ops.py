"""jit'd wrapper: assemble Eq. (1) operands from a Batch + predict fused.

Drop-in replacement for core.model.predict's forward value — inference /
eval only.  The *training* hot path behind ``FitConfig.use_kernels`` does
not route through here: `sgd.train_epoch_scheduled` uses the fused
`kernels/mf_sgd` step (`apply_culsh_sgd` / `apply_mf_sgd`), which computes
this same forward inside the update kernel.  Gathers happen at XLA level,
the fused reduction in the Pallas kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.model import Batch, Params
from repro.kernels.neighbor_predict.kernel import neighbor_predict


def predict_batch(p: Params, bt: Batch, *, interpret: bool):
    bbar = p.mu + p.b[bt.i] + p.bh[bt.j]
    bbar_nb = p.mu + p.b[bt.i][:, None] + p.bh[bt.nb]
    resid = (bt.rnb - bbar_nb) * bt.expl
    nR = jnp.sum(bt.expl, 1)
    nN = jnp.sum(bt.impl, 1)
    sR = jnp.where(nR > 0, 1.0 / jnp.sqrt(jnp.maximum(nR, 1.0)), 0.0)
    sN = jnp.where(nN > 0, 1.0 / jnp.sqrt(jnp.maximum(nN, 1.0)), 0.0)
    return neighbor_predict(
        p.U[bt.i], p.V[bt.j], p.W[bt.j], p.C[bt.j], resid, bt.impl,
        bbar, sR, sN, interpret=interpret)
