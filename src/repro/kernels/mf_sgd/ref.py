"""Pure-jnp oracles for the fused SGD steps (CUSGD++ and CULSH-MF).

On CPU these *are* the fast path: `ops` resolves ``impl="auto"`` to the ref
(Pallas only has the interpreter there), mirroring `candidate_score`.
"""
import jax
import jax.numpy as jnp

from repro.core.model import predict_gathered


def mf_sgd_step_ref(u, v, r, valid, gamma_u, gamma_v, lam_u, lam_v, *,
                    bce: bool = False):
    pred = jnp.sum(u * v, axis=-1)
    e = (r - (jax.nn.sigmoid(pred) if bce else pred)) * valid
    eb = e[:, None]
    vm = valid[:, None]
    u2 = u + gamma_u * (eb * v - lam_u * u) * vm
    v2 = v + gamma_v * (eb * u - lam_v * v) * vm
    return u2, v2, e


def culsh_sgd_step_ref(row, col, rnb, bh_nb, expl, r, valid, hp, *,
                       bce: bool = False):
    """Fused six-parameter Eq. (5) step on a conflict-free packed tile.

    Operands are batch-minor (one sample per column, as the kernel takes
    them): ``row [F+1, B]`` = U‖b and ``col [F+2K+1, B]`` = V‖W‖C‖b̂ are
    gathers of the two packed parameter planes (`model.PackedParams`),
    ``rnb``, ``bh_nb`` and ``expl`` are ``[K, B]``, ``r`` and ``valid``
    are ``[B]``; ``hp`` packs the 12 decayed hyper scalars
    ``(γb, γb̂, γu, γv, γw, γc, λb, λb̂, λu, λv, λw, λc)`` plus ``μ``.
    The Eq. (1) forward (including b̄, residuals and the |R|/|N|
    normalizers) happens *inside* the step — only the neighbour baselines
    ``bh_nb`` = b̂[J^K[j]] need the whole b̂ column and are looked up
    outside (`ops.neighbour_baselines`).
    Returns the two updated tiles, batch-minor; `ops.apply_culsh_sgd`
    turns them into one delta-scatter per plane.
    """
    row, col, rnb, bh_nb, expl = (a.T for a in (row, col, rnb, bh_nb, expl))
    F = row.shape[-1] - 1
    K = rnb.shape[-1]
    gb, gbh, gu, gv, gw, gc = (hp[k] for k in range(6))
    lb, lbh, lu, lv, lw, lc = (hp[k] for k in range(6, 12))
    mu = hp[12]
    u, b = row[:, :F], row[:, F]
    v, w = col[:, :F], col[:, F:F + K]
    c, bh = col[:, F + K:F + 2 * K], col[:, F + 2 * K]
    impl = 1.0 - expl
    pred, aux = predict_gathered(mu, b, bh, u, v, w, c, bh_nb,
                                 rnb, expl, impl)
    resid, sR, sN = aux["resid"], aux["sR"], aux["sN"]
    e = (r - (jax.nn.sigmoid(pred) if bce else pred)) * valid
    eb = e[:, None]
    vm = valid[:, None]
    b2 = b + gb * (e - lb * b) * valid
    bh2 = bh + gbh * (e - lbh * bh) * valid
    u2 = u + gu * (eb * v - lu * u) * vm
    v2 = v + gv * (eb * u - lv * v) * vm
    w2 = w + gw * (sR[:, None] * eb * resid - lw * w) * expl * vm
    c2 = c + gc * (sN[:, None] * eb - lc * c) * impl * vm
    row2 = jnp.concatenate([u2, b2[:, None]], axis=1)
    col2 = jnp.concatenate([v2, w2, c2, bh2[:, None]], axis=1)
    return row2.T, col2.T
