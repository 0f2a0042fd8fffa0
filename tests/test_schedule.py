"""Tiered conflict-free scheduler + schedule-ordered assembly + parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import model, sgd
from repro.data.sparse import conflict_free_schedule, from_coo
from repro.kernels.mf_sgd import ops
from repro.kernels.mf_sgd.ops import apply_culsh_sgd, apply_mf_sgd

RNG = np.random.default_rng(0)


def _batches(sched):
    """Yield (kind, width, ids) for every batch of every tier, decoded
    through the schedule-order layout.  Shard cells live at positions
    [0, shard_span); tier/leftover starts are relative to the cf region
    that follows."""
    order = np.asarray(sched.order)
    span = sched.shard_span

    def window(start, width, valid):
        start = int(start)
        v = np.asarray(valid)
        ids = order[start:start + width]
        return ids[v[:len(ids)]]

    ss = np.asarray(sched.shard_starts)
    for d in range(ss.shape[0]):
        for s in range(ss.shape[1]):
            for r in range(ss.shape[2]):
                yield ("shard", (d, s, r), sched.shard_width,
                       window(ss[d, s, r], sched.shard_width,
                              sched.shard_valid[d, s, r]))
    for t, (starts, valid) in enumerate(zip(sched.tier_starts,
                                            sched.tier_valid)):
        for b in range(starts.shape[0]):
            yield ("tier", t, sched.widths[t],
                   window(span + starts[b], sched.widths[t], valid[b]))
    for b in range(sched.lo_starts.shape[0]):
        yield ("lo", b, sched.widths[0],
               window(span + sched.lo_starts[b], sched.widths[0],
                      sched.lo_valid[b]))


def _check_schedule(rows, cols, sched):
    """order is a permutation; every conflict-free batch is conflict-free;
    all batches together cover each triple exactly once."""
    rows, cols = np.asarray(rows), np.asarray(cols)
    order = np.asarray(sched.order)
    assert sorted(order.tolist()) == list(range(len(rows))), "not a cover"
    seen = 0
    for kind, _, _, ids in _batches(sched):
        seen += len(ids)
        if kind != "lo" and len(ids):
            assert len(np.unique(rows[ids])) == len(ids), "row conflict"
            assert len(np.unique(cols[ids])) == len(ids), "col conflict"
    assert seen == len(rows), "batches don't partition the triples"


@settings(max_examples=10, deadline=None)
@given(st.integers(5, 200), st.integers(3, 60), st.integers(16, 256),
       st.integers(0, 10**6))
def test_schedule_conflict_free_exact_cover(M, N, batch, seed):
    rng = np.random.default_rng(seed)
    nnz = min(M * N, int(rng.integers(1, 4 * (M + N))))
    pairs = rng.choice(M * N, size=nnz, replace=False)
    rows = (pairs // N).astype(np.int32)
    cols = (pairs % N).astype(np.int32)
    sched = conflict_free_schedule(rows, cols, batch=batch, M=M, N=N,
                                   seed=seed)
    _check_schedule(rows, cols, sched)


def test_tier_widths_monotone(tiny_sparse):
    sp = tiny_sparse
    for tiers in (1, 2, 3, 4):
        sched = conflict_free_schedule(
            np.asarray(sp.rows), np.asarray(sp.cols), batch=128,
            tiers=tiers, M=sp.M, N=sp.N, seed=0)
        assert len(sched.widths) == tiers
        assert all(a > b for a, b in zip(sched.widths, sched.widths[1:])), \
            "tier widths must strictly decrease"
        assert all(w == max(1, sched.widths[0] >> t)
                   for t, w in enumerate(sched.widths))
        assert sched.pad_width == sched.widths[0]


def test_schedule_zipf_dataset(tiny_sparse):
    sp = tiny_sparse
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=128, M=sp.M, N=sp.N, seed=0)
    _check_schedule(sp.rows, sp.cols, sched)
    st_ = sched.stats()
    # tiering recovers the zipf tail: the single-width scheduler managed
    # cf_frac ≈ 0.5–0.6 here, the tiered one must clear the bench floor
    assert st_["cf_frac"] >= 0.8
    assert st_["n_cf"] + st_["n_lo"] == sp.nnz
    # stats are self-describing: every tier + leftover fill reported
    assert len(st_["tiers"]) == len(sched.widths)
    assert 0.0 <= st_["lo_fill"] <= 1.0
    for t in st_["tiers"]:
        assert t["n"] <= t["rounds"] * t["width"]


def test_sharded_schedule_block_aligned(tiny_sparse):
    """Shard-tier batches only touch block ((d+s) % D, d) — the disjointness
    that lets shard_map scan a step's D batches with no collective.  Blocks
    are cut at the (nnz-balanced) row/col bounds and every id remaps into
    a contiguous equal-size block-padded range."""
    sp = tiny_sparse
    D = 4
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=64, M=sp.M, N=sp.N, shards=D, seed=0)
    _check_schedule(sp.rows, sp.cols, sched)
    rb_bounds = np.asarray(sched.row_bounds)
    cb_bounds = np.asarray(sched.col_bounds)
    assert sched.shards == D and rb_bounds.shape == (D + 1,)
    assert rb_bounds[-1] == sp.M and cb_bounds[-1] == sp.N
    assert sched.block_rows == np.diff(rb_bounds).max()
    rows, cols = np.asarray(sp.rows), np.asarray(sp.cols)
    n_shard = 0
    for kind, key, _, ids in _batches(sched):
        if kind != "shard" or not len(ids):
            continue
        d, s, _ = key
        n_shard += len(ids)
        blk_r = np.searchsorted(rb_bounds, rows[ids], side="right") - 1
        blk_c = np.searchsorted(cb_bounds, cols[ids], side="right") - 1
        assert (blk_r == (d + s) % D).all()
        assert (blk_c == d).all()
    assert n_shard > 0, "shard tier empty on zipf data"
    # the id maps re-lay each block into [d·block, d·block + extent):
    # strictly monotone (order-preserving), block-contiguous, injective
    rm = np.asarray(sched.row_map)
    assert rm.shape == (sp.M,) and (np.diff(rm) > 0).all()
    for d in range(D):
        seg = rm[rb_bounds[d]:rb_bounds[d + 1]]
        assert seg[0] == d * sched.block_rows
        assert seg[-1] < (d + 1) * sched.block_rows


def test_nnz_balanced_blocks_beat_equal_range(tiny_sparse):
    """Equal-nnz block bounds on zipf data: still an exact conflict-free
    cover, and the shard tier schedules more triples at better fill than
    the legacy equal-id-range cut (whose head blocks hog the round budget
    and leave tail-block rounds empty)."""
    sp = tiny_sparse
    rows, cols = np.asarray(sp.rows), np.asarray(sp.cols)
    kw = dict(batch=64, M=sp.M, N=sp.N, shards=4, seed=0)
    bal = conflict_free_schedule(rows, cols, balance_blocks=True, **kw)
    eq = conflict_free_schedule(rows, cols, balance_blocks=False, **kw)
    _check_schedule(rows, cols, bal)
    _check_schedule(rows, cols, eq)
    s_bal, s_eq = bal.stats()["shard"], eq.stats()["shard"]
    assert s_bal["fill"] > s_eq["fill"], (s_bal["fill"], s_eq["fill"])
    # fewer padded rounds = fewer scan steps for the same coverage
    assert s_bal["rounds"] < s_eq["rounds"], (s_bal["rounds"], s_eq["rounds"])
    assert s_bal["n"] >= 0.98 * s_eq["n"], (s_bal["n"], s_eq["n"])
    # balanced cuts strictly shrink the heaviest block's nnz share (full
    # equality is unreachable: extents are floored at the round width so
    # head-cell matchings aren't extent-capped)
    dr = np.bincount(rows, minlength=sp.M)
    heaviest = lambda sched_: max(
        dr[a:b].sum() for a, b in zip(np.asarray(sched_.row_bounds)[:-1],
                                      np.asarray(sched_.row_bounds)[1:]))
    assert heaviest(bal) < heaviest(eq), (heaviest(bal), heaviest(eq))


def test_scheduled_data_matches_assemble(tiny_sparse):
    """slice_batch over ScheduledData == assemble on the same triples."""
    sp = tiny_sparse
    K = 8
    JK = jnp.asarray(RNG.integers(0, sp.N, (sp.N, K)), jnp.int32)
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=128, M=sp.M, N=sp.N, seed=0)
    sd = model.build_scheduled_data(sp, JK, sched)
    order = jnp.asarray(sched.order)
    for t, (starts, valid) in enumerate(zip(sched.tier_starts,
                                            sched.tier_valid)):
        if not starts.shape[0]:
            continue
        b = int(RNG.integers(0, starts.shape[0]))
        W = sched.widths[t]
        got = model.slice_batch(sd, starts[b], W, valid[b])
        idx = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([order, jnp.zeros(W, jnp.int32)]), starts[b], W)
        want = model.assemble(sp, JK, idx, valid[b])
        for f in ("i", "j", "r", "nb", "rnb", "expl", "impl"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)) * np.asarray(valid[b]).reshape(
                    (-1,) + (1,) * (getattr(got, f).ndim - 1)),
                np.asarray(getattr(want, f)) * np.asarray(valid[b]).reshape(
                    (-1,) + (1,) * (getattr(want, f).ndim - 1)),
                err_msg=f"tier {t} field {f}")


def test_eval_cache_matches_rmse(tiny_sparse):
    sp = tiny_sparse
    K = 8
    JK = jnp.asarray(RNG.integers(0, sp.N, (sp.N, K)), jnp.int32)
    p = model.init_from_data(jax.random.PRNGKey(0), sp, 8, K)
    n = 700
    te_r = jnp.asarray(RNG.integers(0, sp.M, n), jnp.int32)
    te_c = jnp.asarray(RNG.integers(0, sp.N, n), jnp.int32)
    te_v = jnp.asarray(RNG.uniform(1, 5, n), jnp.float32)
    ec = model.build_eval_cache(sp, JK, te_r, te_c, chunk=256)
    want = float(model.rmse(p, sp, JK, te_r, te_c, te_v))
    got = float(model.rmse_cached(p, ec, te_r, te_c, te_v))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # mf_only path: zero-width cache, predict_mf only
    ec0 = model.build_eval_cache(sp, JK, te_r, te_c, mf_only=True)
    want0 = float(model.rmse(p, sp, JK, te_r, te_c, te_v, mf_only=True))
    got0 = float(model.rmse_cached(p, ec0, te_r, te_c, te_v, mf_only=True))
    np.testing.assert_allclose(got0, want0, rtol=1e-6)


def _conflict_free_batch(sp, K, B=64, seed=0):
    """A batch with each row/col at most once, assembled from real triples."""
    rng = np.random.default_rng(seed)
    rows, cols = np.asarray(sp.rows), np.asarray(sp.cols)
    order = rng.permutation(sp.nnz)
    take, ri, ci = [], set(), set()
    for t in order:
        if rows[t] not in ri and cols[t] not in ci:
            take.append(t)
            ri.add(rows[t])
            ci.add(cols[t])
        if len(take) == B:
            break
    idx = jnp.asarray(take, jnp.int32)
    JK = jnp.asarray(rng.integers(0, sp.N, (sp.N, K)), jnp.int32)
    return JK, idx, jnp.ones((len(take),), bool)


def test_conflict_free_step_matches_scaled(tiny_sparse):
    """On a conflict-free batch all collision counts are 1, so the fast
    path must agree with the scaled path exactly."""
    sp = tiny_sparse
    JK, idx, valid = _conflict_free_batch(sp, K=4)
    bt = model.assemble(sp, JK, idx, valid)
    p = model.init_from_data(jax.random.PRNGKey(0), sp, 8, 4)
    hp = sgd.Hyper()
    d = jnp.float32(1.0)
    for step in (sgd.culsh_step, sgd.mf_step):
        fast = step(p, bt, hp, d, conflict_free=True)
        scaled = step(p, bt, hp, d, conflict_free=False)
        for leaf_f, leaf_s in zip(jax.tree.leaves(fast), jax.tree.leaves(scaled)):
            np.testing.assert_allclose(np.asarray(leaf_f), np.asarray(leaf_s),
                                       rtol=1e-6, atol=1e-7)


def test_packed_step_bit_identical(tiny_sparse):
    """The packed-plane steps are *bit-identical* to the unpacked
    reference steps — on conflict-free batches, on collision-scaled
    batches, and with the schedule-precomputed collision normalizers."""
    sp = tiny_sparse
    hp = sgd.Hyper()
    d = jnp.float32(0.9)
    # conflict-free batch
    JK, idx, valid = _conflict_free_batch(sp, K=4, seed=11)
    bt = model.assemble(sp, JK, idx, valid)
    p = model.init_from_data(jax.random.PRNGKey(3), sp, 8, 4)
    pp = model.pack_params(p)
    for f in ("U", "V", "b", "bh", "W", "C"):   # pack∘unpack round-trips
        np.testing.assert_array_equal(
            np.asarray(getattr(model.unpack_params(pp), f)),
            np.asarray(getattr(p, f)), err_msg=f"roundtrip:{f}")
    cases = [
        (sgd.culsh_step(p, bt, hp, d, conflict_free=True),
         sgd.culsh_step_packed(pp, bt, hp, d, conflict_free=True), "cf"),
        (sgd.mf_step(p, bt, hp, d, conflict_free=True),
         sgd.mf_step_packed(pp, bt, hp, d, conflict_free=True), "mf"),
    ]
    # collision-ful batch (repeated rows/cols) — the scaled path
    rng = np.random.default_rng(5)
    ridx = jnp.asarray(rng.integers(0, sp.nnz, 96), jnp.int32)
    btc = model.assemble(sp, JK, ridx, jnp.ones((96,), bool))
    cases.append((sgd.culsh_step(p, btc, hp, d, conflict_free=False),
                  sgd.culsh_step_packed(pp, btc, hp, d, conflict_free=False),
                  "scaled"))
    # precomputed normalizers (host 1/count, as in EpochSchedule.lo_scale_*)
    ri, ci = np.asarray(btc.i), np.asarray(btc.j)
    inv_count = lambda ids: jnp.asarray(
        (np.float32(1.0)
         / np.unique(ids, return_counts=True)[1].astype(np.float32)[
             np.unique(ids, return_inverse=True)[1]]))
    cases.append((sgd.culsh_step(p, btc, hp, d, conflict_free=False),
                  sgd.culsh_step_packed(pp, btc, hp, d,
                                        scales=(inv_count(ri),
                                                inv_count(ci))),
                  "precomputed-scales"))
    for want, got_pp, tag in cases:
        got = model.unpack_params(got_pp)
        for f in ("U", "V", "b", "bh", "W", "C"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                err_msg=f"{tag}:{f}")


def test_fused_kernel_matches_culsh_step(tiny_sparse):
    sp = tiny_sparse
    JK, idx, valid = _conflict_free_batch(sp, K=4)
    bt = model.assemble(sp, JK, idx, valid)
    p = model.init_from_data(jax.random.PRNGKey(1), sp, 8, 4)
    pp = model.pack_params(p)
    hp = sgd.Hyper()
    d = jnp.float32(0.7)
    want = sgd.culsh_step(p, bt, hp, d, conflict_free=True)
    for impl in ("ref", "pallas"):
        got = model.unpack_params(
            apply_culsh_sgd(pp, bt, hp, d, impl=impl, interpret=True))
        for f in ("b", "bh", "U", "V", "W", "C"):
            np.testing.assert_allclose(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                rtol=1e-5, atol=1e-5, err_msg=f"{impl}:{f}")


def test_kernels_width_generic(tiny_sparse):
    """Every tier width routes through the fused kernels: narrow batches
    (width ≪ tile) stay exact with the tile clamped to the batch."""
    sp = tiny_sparse
    hp = sgd.Hyper()
    d = jnp.float32(1.0)
    for B in (7, 24, 96, 250):
        JK, idx, valid = _conflict_free_batch(sp, K=4, B=B, seed=B)
        bt = model.assemble(sp, JK, idx, valid)
        p = model.init_from_data(jax.random.PRNGKey(B), sp, 8, 4)
        pp = model.pack_params(p)
        want = sgd.culsh_step(p, bt, hp, d, conflict_free=True)
        for impl in ("ref", "pallas"):
            got = model.unpack_params(
                apply_culsh_sgd(pp, bt, hp, d, impl=impl, tile_b=256,
                                interpret=True))
            for f in ("b", "bh", "U", "V", "W", "C"):
                np.testing.assert_allclose(
                    np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                    rtol=1e-5, atol=1e-5, err_msg=f"B={B} {impl}:{f}")
        got_mf = model.unpack_params(
            apply_mf_sgd(pp, bt, hp, d, impl="pallas", tile_b=256,
                         interpret=True))
        want_mf = sgd.mf_step(p, bt, hp, d, conflict_free=True)
        np.testing.assert_allclose(np.asarray(got_mf.U), np.asarray(want_mf.U),
                                   rtol=1e-5, atol=1e-6, err_msg=f"B={B} mf")


def test_mf_kernel_matches_mf_step(tiny_sparse):
    sp = tiny_sparse
    JK, idx, valid = _conflict_free_batch(sp, K=4, seed=3)
    bt = model.assemble(sp, JK, idx, valid)
    p = model.init_from_data(jax.random.PRNGKey(2), sp, 8, 4)
    pp = model.pack_params(p)
    hp = sgd.Hyper()
    d = jnp.float32(1.0)
    want = sgd.mf_step(p, bt, hp, d, conflict_free=True)
    for impl in ("ref", "pallas"):
        got = model.unpack_params(
            apply_mf_sgd(pp, bt, hp, d, impl=impl, interpret=True))
        np.testing.assert_allclose(np.asarray(got.U), np.asarray(want.U),
                                   rtol=1e-5, atol=1e-6, err_msg=impl)
        np.testing.assert_allclose(np.asarray(got.V), np.asarray(want.V),
                                   rtol=1e-5, atol=1e-6, err_msg=impl)


def _epoch_setup(sp, K=4):
    """Neighbour lists, a tiered schedule with leftovers, its ordered data
    and packed initial planes for `train_epoch_scheduled` runs."""
    JK = jnp.asarray(np.random.default_rng(0).integers(0, sp.N, (sp.N, K)),
                     jnp.int32)
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=128, M=sp.M, N=sp.N, seed=0)
    sd = model.build_scheduled_data(sp, JK, sched)
    p0 = model.init_from_data(jax.random.PRNGKey(0), sp, 8, K)
    return JK, sched, sd, model.pack_params(p0)


def _copy(p):
    return jax.tree.map(jnp.copy, p)


def test_scheduled_epoch_learns_and_matches_unscheduled(tiny_sparse):
    """train_epoch_scheduled drops the loss like train_epoch does, and the
    kernel path is bit-identical to the jnp scheduled path on CPU."""
    sp = tiny_sparse
    JK, sched, sd, pp0 = _epoch_setup(sp)
    hp = sgd.Hyper()
    key = jax.random.PRNGKey(1)

    def sse(pp):
        pred, _ = model.predict(model.unpack_params(pp), model.assemble(
            sp, JK, jnp.arange(sp.nnz, dtype=jnp.int32),
            jnp.ones((sp.nnz,), bool)))
        return float(jnp.mean((sp.vals - pred) ** 2))

    base = sse(pp0)
    p1 = p2 = None
    for ep in range(2):
        kk = jax.random.fold_in(key, ep)
        ee = jnp.asarray(ep)
        p1 = sgd.train_epoch_scheduled(_copy(pp0) if p1 is None else p1,
                                       sd, sched, kk, ee, hp)
        p2 = sgd.train_epoch_scheduled(_copy(pp0) if p2 is None else p2,
                                       sd, sched, kk, ee, hp,
                                       use_kernels=True, impl="ref")
    assert sse(p1) < base
    for l1, l2 in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("use_kernels,impl", [
    (False, "ref"), (True, "ref"), (True, "pallas")],
    ids=["jnp", "kernel-ref", "kernel-pallas"])
def test_scheduled_epoch_nb_bias_lookup_bit_equal(tiny_sparse, monkeypatch,
                                                   use_kernels, impl):
    """An epoch whose neighbour b̂ is looked up one-hot returns planes
    bit-equal to the epoch that gathers it: every width tier (through the
    kernel step, or the jnp one) and the leftover tier."""
    JK, sched, sd, pp0 = _epoch_setup(tiny_sparse)
    assert len(sched.tier_starts) > 1 and sched.lo_starts.shape[0] > 0
    out = {}
    # the path is chosen as the epoch is traced, so each forced path needs
    # a fresh trace; a CPU program would otherwise keep the gather
    for path, per_lookup in (("gather", 0), ("onehot", 1 << 30)):
        monkeypatch.setattr(ops, "ONEHOT_ITEMS_PER_LOOKUP", per_lookup)
        if path == "onehot":
            monkeypatch.setattr(ops, "_nb_bias_gather", ops._nb_bias_onehot)
        jax.clear_caches()
        hlo = jax.jit(lambda p: ops.neighbour_baselines(p.bh, JK)).lower(
            pp0).compile().as_text()
        assert (" dot(" in hlo) == (path == "onehot")
        out[path] = sgd.train_epoch_scheduled(
            _copy(pp0), sd, sched, jax.random.PRNGKey(3), jnp.asarray(0),
            sgd.Hyper(), use_kernels=use_kernels, impl=impl, interpret=True)
    monkeypatch.undo()
    jax.clear_caches()
    for plane in ("row", "col"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out["onehot"], plane)).view(np.int32),
            np.asarray(getattr(out["gather"], plane)).view(np.int32),
            err_msg=plane)
