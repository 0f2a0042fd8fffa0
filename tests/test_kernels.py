"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.mf_sgd.kernel import culsh_sgd_step, mf_sgd_step
from repro.kernels.mf_sgd.ref import culsh_sgd_step_ref, mf_sgd_step_ref
from repro.kernels.neighbor_predict.kernel import neighbor_predict
from repro.kernels.neighbor_predict.ref import neighbor_predict_ref
from repro.kernels.simlsh_encode.kernel import simlsh_encode
from repro.kernels.simlsh_encode.ref import simlsh_encode_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("N,deg,bits,tile", [
    (8, 16, 16, 8), (37, 64, 24, 8), (128, 32, 30, 16), (5, 8, 8, 8),
])
def test_simlsh_encode_shapes(N, deg, bits, tile):
    psi = jnp.asarray(RNG.normal(size=(N, deg)).astype(np.float32))
    phi = jnp.asarray(RNG.choice([-1., 1.], size=(N, deg, bits)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(simlsh_encode(psi, phi, tile_n=tile, interpret=True)),
        np.asarray(simlsh_encode_ref(psi, phi)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("B,F,K,tile", [
    (64, 16, 8, 32), (100, 32, 16, 128), (3, 8, 4, 8), (256, 128, 32, 64),
])
def test_neighbor_predict_shapes(B, F, K, tile, dtype):
    a = lambda *s: jnp.asarray(RNG.normal(size=s).astype(dtype))
    args = (a(B, F), a(B, F), a(B, K), a(B, K), a(B, K), a(B, K),
            a(B), a(B), a(B))
    np.testing.assert_allclose(
        np.asarray(neighbor_predict(*args, tile_b=tile, interpret=True)),
        np.asarray(neighbor_predict_ref(*args)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,F,tile", [(32, 8, 16), (200, 32, 64), (7, 16, 8)])
def test_mf_sgd_shapes(B, F, tile):
    a = lambda *s: jnp.asarray(RNG.normal(size=s).astype(np.float32))
    u, v, r = a(B, F), a(B, F), a(B)
    valid = jnp.asarray(RNG.integers(0, 2, B).astype(np.float32))
    got = mf_sgd_step(u, v, r, valid, 0.02, 0.03, 0.01, 0.02, tile_b=tile,
                      interpret=True)
    want = mf_sgd_step_ref(u, v, r, valid, 0.02, 0.03, 0.01, 0.02)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 50), st.integers(1, 20), st.integers(0, 10**6))
def test_neighbor_predict_property(B, K, seed):
    rng = np.random.default_rng(seed)
    F = 8
    a = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    args = (a(B, F), a(B, F), a(B, K), a(B, K), a(B, K), a(B, K),
            a(B), a(B), a(B))
    np.testing.assert_allclose(
        np.asarray(neighbor_predict(*args, tile_b=16, interpret=True)),
        np.asarray(neighbor_predict_ref(*args)), rtol=1e-4, atol=1e-4)


def _culsh_args(B, F, K, rng):
    """Batch-minor packed-plane operands: (row [F+1,B], col [F+2K+1,B],
    rnb, bh_nb, expl [K,B], r, valid [B], hp[13]) — see
    `mf_sgd.ref.culsh_sgd_step_ref`."""
    a = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    expl = jnp.asarray(rng.integers(0, 2, (K, B)).astype(np.float32))
    valid = jnp.asarray(rng.integers(0, 2, B).astype(np.float32))
    hp = jnp.concatenate([jnp.abs(a(12)) * 0.05, a(1) * 0.1])
    return (a(F + 1, B), a(F + 2 * K + 1, B), a(K, B), a(K, B), expl,
            a(B), valid, hp)


@pytest.mark.parametrize("bce", [False, True])
@pytest.mark.parametrize("B,F,K,tile", [
    (64, 16, 8, 32), (100, 32, 16, 128), (3, 8, 4, 8),
])
def test_culsh_sgd_shapes(B, F, K, tile, bce):
    args = _culsh_args(B, F, K, np.random.default_rng(B * 7 + K))
    got = culsh_sgd_step(*args, tile_b=tile, bce=bce, interpret=True)
    want = culsh_sgd_step_ref(*args, bce=bce)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_culsh_sgd_invalid_rows_untouched():
    args = _culsh_args(16, 8, 4, np.random.default_rng(0))
    args = args[:6] + (jnp.zeros((16,), jnp.float32),) + args[7:]
    row2, col2 = culsh_sgd_step(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(row2), np.asarray(args[0]))
    np.testing.assert_allclose(np.asarray(col2), np.asarray(args[1]))


def test_mf_sgd_invalid_rows_untouched():
    a = lambda *s: jnp.asarray(RNG.normal(size=s).astype(np.float32))
    u, v, r = a(16, 8), a(16, 8), a(16)
    valid = jnp.zeros((16,), jnp.float32)
    u2, v2, e = mf_sgd_step(u, v, r, valid, 0.1, 0.1, 0.1, 0.1,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(u2), np.asarray(u))
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v))
    np.testing.assert_allclose(np.asarray(e), 0.0)


def test_ops_encode_band_matches_core(tiny_sparse):
    from repro.core.simlsh import SimLSHConfig, band_accumulate
    from repro.kernels.simlsh_encode.ops import encode_band
    sp = tiny_sparse
    maxdeg = int(np.bincount(np.asarray(sp.cols), minlength=sp.N).max())
    deg = ((maxdeg + 7) // 8) * 8
    cfg = SimLSHConfig(G=8, p=2, q=2)
    key = jax.random.PRNGKey(0)
    S_k = encode_band(sp, cfg, key, jnp.asarray(1), deg=deg, interpret=True)
    S_r = band_accumulate(sp.rows, sp.cols, sp.vals, key, jnp.asarray(1),
                          N=sp.N, bits=cfg.sig_bits, psi_pow=cfg.psi_pow)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_r),
                               rtol=1e-4, atol=1e-3)


def test_ops_predict_matches_model(tiny_sparse):
    from repro.core import model
    from repro.core.model import assemble
    from repro.kernels.neighbor_predict.ops import predict_batch
    sp = tiny_sparse
    p = model.init_from_data(jax.random.PRNGKey(0), sp, 8, 4)
    JK = jnp.asarray(RNG.integers(0, sp.N, (sp.N, 4)), jnp.int32)
    idx = jnp.arange(256, dtype=jnp.int32)
    bt = assemble(sp, JK, idx, jnp.ones((256,), bool))
    got = predict_batch(p, bt, interpret=True)
    want, _ = model.predict(p, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
