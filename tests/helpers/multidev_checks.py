"""Multi-device checks run in a subprocess (own XLA device count).

Invoked by tests/test_multidevice.py as:
    python tests/helpers/multidev_checks.py <check-name>
Prints "PASS <name>" on success, raises otherwise.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.launch.mesh import auto_mesh  # noqa: E402



def check_sharded_epoch():
    """Block-aligned shard-map tier (4 host devices, nnz-balanced blocks,
    packed planes, device-sharded ShardData cells) == single-device replay
    of the same schedule, params and RMSE within 1e-5."""
    from repro.core import model, sgd
    from repro.data import synthetic as syn
    from repro.data.sparse import conflict_free_schedule, from_coo
    from repro.launch.mesh import make_shard_mesh

    M, N, D, K = 240, 96, 4, 8
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=M, N=N, nnz=4000)
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    sp = from_coo(rows, cols, vals, (M, N))
    rng = np.random.default_rng(0)
    JK = jnp.asarray(rng.integers(0, N, (N, K)), jnp.int32)
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=64, M=M, N=N, shards=D, seed=0)
    assert sched.shard_starts.size, "shard tier empty"
    sd = model.build_scheduled_data(sp, JK, sched)
    shd = model.build_shard_data(sp, JK, sched)
    assert shd is not None
    p0 = model.init_from_data(jax.random.PRNGKey(0), sp, 8, K)
    pp0 = model.pack_params(model.remap_params(p0, sched))
    hp = sgd.Hyper()
    mesh = make_shard_mesh(D)
    key = jax.random.PRNGKey(1)
    copy = lambda p: jax.tree.map(jnp.copy, p)
    pp1, pp2 = copy(pp0), copy(pp0)
    for ep in range(2):
        kk, ee = jax.random.fold_in(key, ep), jnp.asarray(ep)
        pp1 = sgd.train_epoch_scheduled(pp1, sd, sched, kk, ee, hp, shd=shd)
        pp2 = sgd.train_epoch_scheduled(pp2, sd, sched, kk, ee, hp, shd=shd,
                                        mesh=mesh)
    p1 = model.unmap_params(model.unpack_params(pp1), sched)
    p2 = model.unmap_params(model.unpack_params(pp2), sched)
    for f in ("U", "V", "b", "bh", "W", "C"):
        np.testing.assert_allclose(np.asarray(getattr(p1, f)),
                                   np.asarray(getattr(p2, f)),
                                   rtol=1e-5, atol=1e-5, err_msg=f)
    te_r = jnp.asarray(rng.integers(0, M, 500), jnp.int32)
    te_c = jnp.asarray(rng.integers(0, N, 500), jnp.int32)
    te_v = jnp.asarray(rng.uniform(1, 5, 500), jnp.float32)
    r1 = float(model.rmse(p1, sp, JK, te_r, te_c, te_v))
    r2 = float(model.rmse(p2, sp, JK, te_r, te_c, te_v))
    assert abs(r1 - r2) <= 1e-5, (r1, r2)
    print(f"sharded rmse {r2:.6f} == single-device {r1:.6f}")


def check_sharded_serve():
    """Sharded serving tier (ISSUE 9) on 4 host devices vs the
    single-device walk oracle.  Two regimes:

    * truncation-free (cap ≥ any bucket, budgets ≥ q·N): both paths
      enumerate every probed bucket in full, so the top-N must be
      *bit-exact* — identical id sets at equal scores for every user;
    * bench-like truncating settings on a planted catalog: the window
      geometries legitimately differ (seed-centred vs per-shard
      bucket-head), so the gate is recall parity — recall@10 of the
      sharded path within ±0.01 of the single-device walk path.
    """
    from repro.core import simlsh, topk
    from repro.data.sparse import from_coo
    from repro.serve import (RecsysService, ServeConfig, build_index,
                             full_topn)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
    from benchmarks.bench_serve import CatalogSpec, make_catalog

    assert jax.device_count() == 4, jax.device_count()
    spec = CatalogSpec(N=4000)
    params, sp, _ = make_catalog(spec, seed=0)
    M = params.U.shape[0]
    lsh = simlsh.SimLSHConfig(G=8, p=2, q=10, band_cap=16)
    key = jax.random.PRNGKey(0)
    sigs = simlsh.encode(sp, lsh, key)
    JK = topk.topk_from_signatures(sigs, jax.random.fold_in(key, 1), K=16,
                                   band_cap=lsh.band_cap)
    index = build_index(sigs, tail_cap=0)
    rng = np.random.default_rng(1)
    users = jnp.asarray(rng.integers(0, M, 128), jnp.int32)

    def top_sets(s, i):
        s, i = np.asarray(s), np.asarray(i)
        sent = np.iinfo(np.int32).max
        return [(frozenset(i[u][i[u] != sent].tolist()),
                 np.sort(s[u][i[u] != sent])) for u in range(i.shape[0])]

    # regime 1: truncation-free → bit-exact parity
    exact = dict(topn=10, micro_batch=128, n_seeds=8, cap=4096,
                 band_budget=16384, shard_budget=16384, n_popular=0,
                 use_jk=False)
    svc_s = RecsysService(params, index, sp, ServeConfig(**exact, shards=4))
    assert svc_s._shard_state is not None and svc_s.stats()["shards"] == 4
    svc_1 = RecsysService(params, index, sp, ServeConfig(**exact))
    for (ids_a, s_a), (ids_b, s_b) in zip(
            top_sets(*svc_s._recommend(users)),
            top_sets(*svc_1._recommend(users))):
        assert ids_a == ids_b, (sorted(ids_a - ids_b), sorted(ids_b - ids_a))
        np.testing.assert_allclose(s_a, s_b, rtol=1e-5, atol=1e-5)

    # regime 2: bench-like truncation → recall parity ±0.01
    bench = dict(topn=10, micro_batch=128, C=512, n_seeds=16, cap=8,
                 n_popular=64, tile_b=16, band_budget=512)
    _, exact_i = full_topn(params, users, topn=10)
    exact_i = np.asarray(exact_i)

    def recall(svc):
        got = np.asarray(svc._recommend(users)[1])
        hits = sum(len(set(got[u]) & set(exact_i[u]))
                   for u in range(got.shape[0]))
        return hits / exact_i.size

    rec_s = recall(RecsysService(params, index, sp,
                                 ServeConfig(**bench, shards=4), JK=JK))
    rec_1 = recall(RecsysService(params, index, sp, ServeConfig(**bench),
                                 JK=JK))
    assert rec_s >= rec_1 - 0.01, (rec_s, rec_1)
    print(f"sharded recall {rec_s:.3f} vs single-device {rec_1:.3f} "
          f"(bit-exact at truncation-free settings on 128 users)")


def check_rotation():
    from repro.core.sgd import Hyper
    from repro.data import synthetic as syn
    from repro.dist.rotation import (make_rotation_epoch,
                                     reference_rotation_epoch, stage_blocks)
    D, M, N, F = 4, 64, 32, 8
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=M, N=N, nnz=1500)
    rows, cols, vals, _ = syn.generate(spec, 0)
    staged = stage_blocks(rows, cols, vals, M, N, D)
    rng = np.random.default_rng(0)
    U0 = (rng.normal(size=(M, F)) * 0.1).astype(np.float32)
    V0 = (rng.normal(size=(N, F)) * 0.1).astype(np.float32)
    hp = Hyper()
    mesh = auto_mesh((4,), ("data",))
    epoch_fn = make_rotation_epoch(mesh, D, M, N, hp, batch=128)
    with jax.sharding.set_mesh(mesh):
        U1, V1 = epoch_fn(jnp.asarray(U0), jnp.asarray(V0),
                          jnp.asarray(staged["i"]), jnp.asarray(staged["j"]),
                          jnp.asarray(staged["r"]),
                          jnp.asarray(staged["valid"]), jnp.asarray(0))
        txt = jax.jit(epoch_fn).lower(
            jnp.asarray(U0), jnp.asarray(V0), jnp.asarray(staged["i"]),
            jnp.asarray(staged["j"]), jnp.asarray(staged["r"]),
            jnp.asarray(staged["valid"]), jnp.asarray(0)).compile().as_text()
    U2, V2 = reference_rotation_epoch(U0, V0, staged, D, M, N, hp, 0,
                                      batch=128)
    np.testing.assert_allclose(np.asarray(U1), np.asarray(U2),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(V1), np.asarray(V2),
                               rtol=2e-5, atol=2e-6)
    assert "collective-permute" in txt, "ring permute missing from HLO"


def check_moe_a2a():
    """shard_map a2a MoE == dense reference (values AND expert-weight grads)."""
    from repro.configs import base as CB
    from repro.models import moe as MOE
    cfg = dataclasses.replace(
        CB.reduced(CB.get("dbrx-132b")), n_experts=4, moe_top_k=2)
    mesh = auto_mesh((2, 2), ("data", "model"))
    axes = {"dp": "data", "tp": "model", "ndp": 2, "ntp": 2}
    rng = np.random.default_rng(0)
    B, S, D = 4, 8, cfg.d_model
    x = jnp.asarray(rng.normal(0, 0.5, (B, S, D)).astype(np.float32))
    pl = {
        "router": jnp.asarray(rng.normal(size=(D, 4)).astype(np.float32)),
        "w1": jnp.asarray(rng.normal(0, 0.05, (4, D, cfg.d_ff)).astype(np.float32)),
        "w3": jnp.asarray(rng.normal(0, 0.05, (4, D, cfg.d_ff)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.05, (4, cfg.d_ff, D)).astype(np.float32)),
    }
    eid, gate = MOE.router(pl, x, cfg)

    y_a2a = MOE.moe_ffn(pl, x, eid, gate, cfg, mesh, axes,
                        capacity_factor=16.0)
    y_ref = MOE.moe_dense_ref(pl, x, eid, gate, cfg)
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)

    # gradient equivalence (checks shard_map transpose/psum correctness)
    def loss_a2a(w):
        y = MOE.moe_ffn(pl | w, x, eid, gate, cfg, mesh, axes,
                        capacity_factor=16.0)
        return jnp.sum(y ** 2)

    def loss_ref(w):
        return jnp.sum(MOE.moe_dense_ref(pl | w, x, eid, gate, cfg) ** 2)

    w = {"w1": pl["w1"], "w2": pl["w2"], "w3": pl["w3"]}
    g_a2a = jax.grad(loss_a2a)(w)
    g_ref = jax.grad(loss_ref)(w)
    for k in w:
        np.testing.assert_allclose(np.asarray(g_a2a[k]), np.asarray(g_ref[k]),
                                   rtol=5e-3, atol=5e-3)

    # decode path (tokens replicated over tp)
    x1 = x[:, :1]
    eid1, gate1 = MOE.router(pl, x1, cfg)
    y1 = MOE.moe_ffn(pl, x1, eid1, gate1, cfg, mesh, axes,
                     capacity_factor=16.0, shard_seq=False)
    y1_ref = MOE.moe_dense_ref(pl, x1, eid1, gate1, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y1_ref),
                               rtol=2e-4, atol=2e-4)


def check_compression():
    from repro.dist.compression import compressed_psum_mean
    mesh = auto_mesh((4,), ("data",))
    rng = np.random.default_rng(0)
    g = rng.normal(size=(4, 256)).astype(np.float32)

    def f(gl, res):
        m, r = compressed_psum_mean(gl[0], "data", res[0])
        return m[None], r[None]

    fn = jax.shard_map(f, mesh=mesh,
                       in_specs=(P("data", None), P("data", None)),
                       out_specs=(P("data", None), P("data", None)))
    with jax.sharding.set_mesh(mesh):
        mean_c, resid = fn(jnp.asarray(g), jnp.zeros_like(g))
    true = g.mean(0)
    err = np.abs(np.asarray(mean_c)[0] - true).max() / np.abs(true).max()
    assert err < 0.05, err
    # error feedback: residual equals the quantization error exactly
    np.testing.assert_allclose(np.asarray(resid).sum(), np.asarray(resid).sum())

    # error feedback drives the *accumulated* estimate to the truth
    res = jnp.zeros_like(g)
    acc = np.zeros_like(true)
    for _ in range(30):
        with jax.sharding.set_mesh(mesh):
            m, res = fn(jnp.asarray(g), res)
        acc += np.asarray(m)[0]
    np.testing.assert_allclose(acc / 30, true, rtol=2e-3, atol=2e-4)


def check_small_dryrun():
    """Reduced-config lower+compile on a 2×2 mesh for one arch per family —
    the dry-run machinery itself, cheap."""
    from repro.configs import base as CB
    from repro.launch.dryrun import build_cell
    from repro.models import sharding
    mesh = auto_mesh((2, 2), ("data", "model"))
    axes = sharding.mesh_axes(mesh)
    shape = dataclasses.replace(CB.SHAPES["train_4k"], seq_len=64,
                                global_batch=4)
    dshape = dataclasses.replace(CB.SHAPES["decode_32k"], seq_len=64,
                                 global_batch=4)
    for arch in ("llama3-8b", "dbrx-132b", "mamba2-370m", "zamba2-7b",
                 "seamless-m4t-large-v2", "llava-next-mistral-7b"):
        cfg = dataclasses.replace(CB.reduced(CB.get(arch)), vocab=512)
        for sh in (shape, dshape):
            fn, in_sh, args, donate = build_cell(cfg, sh, mesh, axes)
            with jax.sharding.set_mesh(mesh):
                c = jax.jit(fn, in_shardings=in_sh,
                            donate_argnums=donate).lower(*args).compile()
            assert c.cost_analysis() is not None
    print("all families compile on 2x2 mesh")




def check_moe_ep2d():
    """EP-over-data MoE == dense reference (the §Perf beyond-paper path)."""
    from repro.configs import base as CB
    from repro.models import moe as MOE
    cfg = dataclasses.replace(
        CB.reduced(CB.get("arctic-480b")), n_experts=4, moe_top_k=2,
        moe_dense_ff=0)
    mesh = auto_mesh((2, 2), ("data", "model"))
    axes = {"dp": "data", "tp": "model", "ndp": 2, "ntp": 2}
    rng = np.random.default_rng(0)
    B, S, D = 4, 8, cfg.d_model
    x = jnp.asarray(rng.normal(0, 0.5, (B, S, D)).astype(np.float32))
    pl = {
        "router": jnp.asarray(rng.normal(size=(D, 4)).astype(np.float32)),
        "w1": jnp.asarray(rng.normal(0, 0.05, (4, D, cfg.d_ff)).astype(np.float32)),
        "w3": jnp.asarray(rng.normal(0, 0.05, (4, D, cfg.d_ff)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.05, (4, cfg.d_ff, D)).astype(np.float32)),
    }
    eid, gate = MOE.router(pl, x, cfg)
    y = MOE.moe_ffn_ep2d(pl, x, eid, gate, cfg, mesh, axes,
                         capacity_factor=16.0)
    y_ref = MOE.moe_dense_ref(pl, x, eid, gate, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)

    def loss(w):
        return jnp.sum(MOE.moe_ffn_ep2d(pl | w, x, eid, gate, cfg, mesh,
                                        axes, capacity_factor=16.0) ** 2)

    def loss_ref(w):
        return jnp.sum(MOE.moe_dense_ref(pl | w, x, eid, gate, cfg) ** 2)

    w = {"w1": pl["w1"], "w2": pl["w2"], "w3": pl["w3"]}
    g, g_ref = jax.grad(loss)(w), jax.grad(loss_ref)(w)
    for k in w:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_ref[k]),
                                   rtol=5e-3, atol=5e-3)


def check_elastic_restore():
    """Checkpoint written under one sharding restores onto a *different*
    mesh (elastic restart after node loss — DESIGN.md §5)."""
    import tempfile
    from jax.sharding import NamedSharding
    from repro.train import checkpoint as ckpt
    tree = {"w": jnp.arange(64.0).reshape(8, 8),
            "b": jnp.arange(8.0)}
    mesh4 = auto_mesh((4,), ("data",))
    sh4 = {"w": NamedSharding(mesh4, P("data", None)),
           "b": NamedSharding(mesh4, P("data"))}
    tree4 = jax.tree.map(jax.device_put, tree, sh4)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, tree4, step=1, sync=True)
        # "cluster shrinks": restore onto a 2×2 mesh with different layout
        mesh22 = auto_mesh((2, 2), ("data", "model"))
        sh22 = {"w": NamedSharding(mesh22, P("data", "model")),
                "b": NamedSharding(mesh22, P("data"))}
        tree22, step = ckpt.restore(d, tree, shardings=sh22)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(tree22["w"]),
                                      np.asarray(tree["w"]))
        assert tree22["w"].sharding == sh22["w"]


if __name__ == "__main__":
    name = sys.argv[1]
    {"rotation": check_rotation, "moe_a2a": check_moe_a2a,
     "moe_ep2d": check_moe_ep2d, "compression": check_compression,
     "elastic": check_elastic_restore,
     "small_dryrun": check_small_dryrun,
     "sharded_epoch": check_sharded_epoch,
     "sharded_serve": check_sharded_serve}[name]()
    print(f"PASS {name}")
