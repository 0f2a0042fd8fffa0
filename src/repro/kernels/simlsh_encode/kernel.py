"""Pallas TPU kernel: simLSH weighted sign-projection (paper Alg. 1, Eq. 3).

Computes, for a tile of items (columns), the pre-sign accumulator

    S[n, g] = Σ_d  Ψ(r)[n, d] · Φ[n, d, g]

over ELL-padded per-item rater lists (degree-padded to ``deg``), i.e. a
batched [1, deg] × [deg, bits] matvec per item — MXU-shaped.  The CUDA
version assigns one thread block per item and warp-shuffles the reduction;
the TPU version tiles (items × deg × bits) into VMEM and lets the MXU do
the contraction (DESIGN.md §2 hardware adaptation).

Grid: items/TILE_N.  Block shapes keep the working set in VMEM:
TILE_N·deg f32 + TILE_N·deg·bits f32 + TILE_N·bits f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_kernel(psi_ref, phi_ref, out_ref):
    # psi  [TILE_N, deg]        — Ψ(r) weights (0 at padding)
    # phi  [TILE_N, deg, bits]  — ±1 rows Φ(H_i) for this item's raters
    # out  [TILE_N, bits]
    psi = psi_ref[...]
    phi = phi_ref[...]
    acc = jnp.einsum("nd,ndb->nb", psi, phi,
                     preferred_element_type=jnp.float32)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def simlsh_encode(psi, phi, *, interpret: bool, tile_n: int = 8):
    """psi [N, deg] f32, phi [N, deg, bits] f32 → S [N, bits] f32."""
    N, deg = psi.shape
    bits = phi.shape[-1]
    pad = (-N) % tile_n
    if pad:
        psi = jnp.pad(psi, ((0, pad), (0, 0)))
        phi = jnp.pad(phi, ((0, pad), (0, 0), (0, 0)))
    Np = psi.shape[0]

    out = pl.pallas_call(
        _encode_kernel,
        grid=(Np // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, deg), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, deg, bits), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, bits), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, bits), jnp.float32),
        interpret=interpret,
    )(psi, phi)
    return out[:N]
