"""Pallas TPU kernel: fused Eq. (1) prediction (CULSH-MF Alg. 3 lines 9–11).

One VMEM pass computes, per sample b of a batch tile:

    pred[b] = b̄[b] + sR[b]·Σ_k resid[b,k]·w[b,k]
                    + sN[b]·Σ_k impl[b,k]·c[b,k]
                    + Σ_f u[b,f]·v[b,f]

The CUDA version keeps {v_j, b̂_j, w_j, c_j} in registers and warp-shuffles
the three reductions; the TPU version tiles the whole sample block into
VMEM and fuses the three contractions in one kernel — same insight
("touch each operand once, reduce in fast memory"), MXU/VPU-shaped
(F and K on the 128-lane axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _predict_kernel(u_ref, v_ref, w_ref, c_ref, resid_ref, impl_ref,
                    bbar_ref, sR_ref, sN_ref, out_ref):
    u = u_ref[...]                # [TB, F]
    v = v_ref[...]
    w = w_ref[...]                # [TB, K]
    c = c_ref[...]
    resid = resid_ref[...]        # [TB, K] (already masked by explicit)
    impl = impl_ref[...]          # [TB, K]
    dot = jnp.sum(u * v, axis=-1)
    expl = jnp.sum(resid * w, axis=-1)
    imp = jnp.sum(impl * c, axis=-1)
    out_ref[...] = bbar_ref[...] + sR_ref[...] * expl + sN_ref[...] * imp + dot


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def neighbor_predict(u, v, w, c, resid, impl, bbar, sR, sN, *,
                     interpret: bool, tile_b: int = 128):
    """All inputs row-aligned on the batch dim B → pred [B] f32."""
    B, F = u.shape
    K = w.shape[1]
    pad = (-B) % tile_b
    if pad:
        padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        u, v, w, c, resid, impl, bbar, sR, sN = map(
            padded, (u, v, w, c, resid, impl, bbar, sR, sN))
    Bp = u.shape[0]
    mat = lambda d: pl.BlockSpec((tile_b, d), lambda i: (i, 0))
    vec = pl.BlockSpec((tile_b,), lambda i: (i,))
    out = pl.pallas_call(
        _predict_kernel,
        grid=(Bp // tile_b,),
        in_specs=[mat(F), mat(F), mat(K), mat(K), mat(K), mat(K),
                  vec, vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((Bp,), jnp.float32),
        interpret=interpret,
    )(u, v, w, c, resid, impl, bbar, sR, sN)
    return out[:B]
