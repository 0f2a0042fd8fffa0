"""The main-path Pallas kernels compile for a TPU v5e at serving and
training widths (`interpret=False`, against a described v5e topology).

Interpret mode checks what a kernel computes, not whether Mosaic accepts
it: tile-alignment rules for blocks and DMAs, SMEM scalar access and VMEM
budgets are only enforced by the TPU compiler.  These compiles run on a
CPU-only host (nothing executes) and take a few seconds each.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sgd
from repro.core.model import Batch, PackedParams
from repro.kernels.candidate_score.kernel import candidate_score_topn
from repro.kernels.lsh_retrieve.kernel import lsh_retrieve_topc
from repro.kernels.mf_sgd.kernel import culsh_sgd_step, mf_sgd_step
from repro.kernels.mf_sgd.ops import apply_culsh_sgd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip — keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt, "kernel missing from compiled program"
    return txt


def test_candidate_score_compiles_at_1m_serve_width(one_chip):
    """B=256 users × C=768 candidates, F+1=49, N=1M lane-padded plane
    (`pack_serve_planes(lanes=128)`), the 1M serve cell's tile_b=16."""
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    B, C, N = 256, 768, 1_000_000
    _compile(lambda u, p, c, m: candidate_score_topn(
        u, p, c, m, topn=10, tile_b=16, interpret=False),
        S((B, 49)), S((N, 128)), S((B, C), jnp.int32), S((B, C)))


def test_lsh_retrieve_compiles_at_1m_serve_width(one_chip):
    """1M serve descriptors: q=10 bands × 16 seeds = 160 windows of cap=8,
    one extra slot (no tail), a 64-item popular exclude set and the
    704-slot walked core of C=768."""
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                           sharding=one_chip)
    B, I, cap, N, q = 256, 160, 8, 1_000_000, 10
    _compile(lambda st, ln, ex, ids, exc: lsh_retrieve_topc(
        st, ln, ex, ids, exc, C=768 - 64, cap=cap, interpret=False),
        S((B, I)), S((B, I)), S((B, 1)), S((q * N + cap,)), S((64,)))


@pytest.mark.parametrize("B", [512, 8])
def test_culsh_sgd_compiles_wide_and_narrow_tiers(one_chip, B):
    """CULSH-MF step at F=K=32, batch-minor: the full 512-wide tier (two
    256-lane tiles) and the narrowest (one 8-lane block)."""
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    F = K = 32
    _compile(lambda *a: culsh_sgd_step(*a, tile_b=256, interpret=False),
             S(F + 1, B), S(F + 2 * K + 1, B), S(K, B), S(K, B), S(K, B),
             S(B), S(B), S(13))


@pytest.mark.parametrize("B", [512, 8])
def test_mf_sgd_compiles_wide_and_narrow_tiers(one_chip, B):
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    F = 32
    _compile(lambda u, v, r, val, hp: mf_sgd_step(
        u, v, r, val, hp[0], hp[1], hp[2], hp[3], tile_b=256,
        interpret=False),
        S(B, F), S(B, F), S(B), S(B), S(4))


@pytest.mark.parametrize("B,kernel", [(512, True), (64, True), (512, False)],
                         ids=["kernel-w512", "kernel-w64", "leftovers"])
def test_culsh_step_reads_neighbour_bias_without_element_gather(
        one_chip, B, kernel):
    """The CULSH step at the MovieLens-10M shape (N=10,677, F=K=32) looks
    the neighbours' b̂ up one-hot: its compiled program gathers whole
    plane rows only — no one-element gather (``slice_sizes={1,1}`` from
    the col plane, or ``{1}`` from b̂) — and the lookup's scope is there."""
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    F = K = 32
    M, N = 27951, 10677
    pp = PackedParams(row=S((M, F + 1)), col=S((N, F + 2 * K + 1)),
                      mu=S(()), F=F, K=K)
    ids = lambda *shape: S(shape, jnp.int32)
    bt = Batch(i=ids(B), j=ids(B), r=S((B,)), nb=ids(B, K), rnb=S((B, K)),
               expl=S((B, K)), impl=S((B, K)), valid=S((B,)))
    hp = sgd.Hyper()
    if kernel:
        step = lambda p, b, d: apply_culsh_sgd(
            p, b, hp, d, impl="pallas", tile_b=256, interpret=False)
        txt = _compile(step, pp, bt, S(()))
    else:   # the leftover tier: scaled jnp step, precomputed normalizers
        step = lambda p, b, d, si, sj: sgd.culsh_step_packed(
            p, b, hp, d, scales=(si, sj))
        txt = jax.jit(step).lower(pp, bt, S(()), S((B,)), S((B,))) \
            .compile().as_text()
    sizes = re.findall(r" gather\(.*?slice_sizes=\{([0-9,]+)\}", txt)
    assert sizes, "no plane-row gather found"
    assert not {"1,1", "1"} & set(sizes), sizes
    assert "gather/nb_bias/" in txt
