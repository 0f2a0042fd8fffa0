"""jit'd wrapper: ELL conversion + kernel dispatch for simLSH encoding.

``encode_band`` reproduces core/simlsh.band_accumulate through the Pallas
kernel: per-item rater lists are ELL-padded (host/XLA side — data movement,
not the hot loop), Φ rows are generated with the same stateless fold_in
scheme, and the kernel does the fused weighted projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.simlsh import SimLSHConfig, phi_rows, psi
from repro.data.sparse import SparseMatrix
from repro.kernels.simlsh_encode.kernel import simlsh_encode


def ell_pack(sp: SparseMatrix, deg: int):
    """Column-major ELL: rater ids + ratings per item, padded to ``deg``.

    Returns (row_ids [N, deg] i32 (0-padded), vals [N, deg] f32 (0-padded)).
    Items with more than ``deg`` raters are truncated (cap documented)."""
    order = jnp.argsort(sp.cols)
    cols_s, rows_s, vals_s = sp.cols[order], sp.rows[order], sp.vals[order]
    first = jnp.searchsorted(cols_s, jnp.arange(sp.N, dtype=cols_s.dtype))
    rank = jnp.arange(sp.rows.shape[0]) - first[cols_s]
    ok = rank < deg
    addr = jnp.where(ok, cols_s * deg + rank, sp.N * deg)
    ids = jnp.zeros((sp.N * deg + 1,), jnp.int32).at[addr].set(rows_s)
    vals = jnp.zeros((sp.N * deg + 1,), jnp.float32).at[addr].set(
        jnp.where(ok, vals_s, 0.0))
    return (ids[:-1].reshape(sp.N, deg),
            vals[:-1].reshape(sp.N, deg))


def encode_band(sp: SparseMatrix, cfg: SimLSHConfig, key, band, *,
                interpret: bool, deg: int = 128):
    """One band's pre-sign accumulators via the Pallas kernel. [N, bits]."""
    ids, vals = ell_pack(sp, deg)
    w = psi(vals, cfg.psi_pow, cfg.psi_mode, cfg.psi_center) * (vals != 0)
    phi = phi_rows(key, band, ids.reshape(-1), cfg.sig_bits)
    phi = phi.reshape(sp.N, deg, cfg.sig_bits)
    return simlsh_encode(w, phi, interpret=interpret)
