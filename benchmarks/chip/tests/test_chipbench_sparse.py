"""The ``train_sparse`` cell's parts at small sizes on the CPU: its sparse
reference against the dense one, its neighbour check, its reader, and
its stop on a program that diverges."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny

SPARSE = "kdd11.culsh_train"
harness = tiny.harness


def _refs():
    _, _, cfg, _ = harness.load_cell(SPARSE)
    return cfg, harness.reference(cfg), harness.reference(
        {"name": "ml10m-culsh"})


def _data(cfg, M, N, nnz, seed=5):
    import gen
    import train_cell
    d = dict(cfg["data"], M=M, N=N, nnz=nnz)
    train, test, _ = gen.ratings(d, seed)
    return d, train, test, train_cell.initial_params(d, 8, 8, train, seed)


def test_sparse_reference_follows_the_dense_one():
    """On the same neighbour lists (the dense reference's exact top-K),
    the sparse reference's RMSE curve and parameters are the dense one's
    to float32 rounding: the join finds what the dense matrix indexes,
    and the scatter-adds sum what the one-hot products do."""
    cfg, sparse, dense = _refs()
    d, train, test, p0 = _data(cfg, 200, 300, 8000)
    M, N = d["M"], d["N"]
    key = jax.random.PRNGKey(3)
    kw = dict(batch=64)
    pd, curve_d, JK = dense.train(p0, train, test, M, N, 8, 3, cfg["hyper"],
                                  key, **kw)
    ps, curve_s, _ = sparse.train(p0, train, test, M, N, 8, 3,
                                  cfg["hyper"], key, JK=JK, **kw)
    np.testing.assert_allclose(curve_s, curve_d, rtol=2e-6)
    for k in pd:
        np.testing.assert_allclose(ps[k], pd[k], rtol=1e-4, atol=1e-5)
    R = sparse.dense(*train, M, N)
    assert float(sparse.rmse(ps, R, JK, *test)) == curve_s[-1]


def test_join_finds_every_rated_neighbour():
    cfg, sparse, dense = _refs()
    d, train, test, _ = _data(cfg, 150, 400, 6000)
    M, N = d["M"], d["N"]
    JK = jax.random.randint(jax.random.PRNGKey(0), (N, 8), 0, N)
    Rd = dense.dense(*train, M, N)
    for rows, cols, _ in (train, test):
        rnb, expl = sparse.join(sparse.dense(*train, M, N), JK, rows, cols)
        want = Rd[rows[:, None], JK[cols]]
        np.testing.assert_array_equal(rnb, want)
        np.testing.assert_array_equal(expl, (want > 0).astype(jnp.float32))
        assert 0 < float(jnp.mean(expl)) < 1


def test_neighbour_gap_of_exact_and_random_lists():
    """The exact top-K of the Ψ = r² cosines read a gap of 0; random
    lists, on a catalog far sparser than its users, read near 1."""
    cfg, sparse, dense = _refs()
    d, train, _, _ = _data(cfg, 300, 3000, 12000)
    N = d["N"]
    exact = dense.neighbours(dense.dense(*train, d["M"], N), K=8)
    assert abs(sparse.neighbour_gap(train, exact, N, 11, 64)) < 1e-5
    rand = jax.random.randint(jax.random.PRNGKey(1), (N, 8), 0, N)
    assert sparse.neighbour_gap(train, rand, N, 11, 64) > 0.8


def test_comparison_trains_the_reference_on_the_program_lists():
    """The comparison hands the reference the program's neighbour lists,
    and ``rmse_lag`` is the signed gap that ``rmse_gap`` holds the size
    of: below 0 where `fit` ends lower than the reference, above 0 where
    it ends higher."""
    from types import SimpleNamespace

    import train_sparse_cell
    from control import patched
    _, _, cfg, _ = harness.load_cell(SPARSE)
    jk_prog = np.arange(12, dtype=np.int32).reshape(4, 3)
    seen = []

    def train(p0, train, test, M, N, K, n, hp, key, *, batch, JK):
        seen.append(np.asarray(JK))
        return p0, [5.0, 4.2, 3.2][:n], JK

    stub = SimpleNamespace(train=train, dense=lambda *a: None,
                           rmse=lambda *a: 3.0,
                           neighbour_gap=lambda *a: 0.5)
    for hist, lag in (([5.0, 4.0, 3.0], -0.0625), ([5.0, 4.2, 3.4], 0.0625)):
        run = harness.Run(cfg=dict(cfg, reference={"batch": 8, "epochs": 3}),
                          traffic={}, seed=2**33 + 5, seconds=1,
                          trace_on=False, t_start=0.0)
        with patched(harness, "reference", lambda cfg: stub):
            train_sparse_cell.compare(run, {}, (None,) * 3, (None,) * 3,
                                      hist, {}, jk_prog)
        np.testing.assert_array_equal(seen[-1], jk_prog)
        assert run.checks["rmse_lag"][0] == pytest.approx(lag)
        assert run.checks["rmse_gap"][0] == pytest.approx(abs(lag))
        assert run.checks["nb_sim_gap"][0] == 0.5
    assert len(seen) == 2


def test_neighbours_reader():
    run = harness.Run(cfg={}, traffic={}, seed=0, seconds=1, trace_on=True,
                      t_start=0.0)
    read = harness.load_module(
        str(tiny.CHIP / "metrics" / "train.neighbours_s.py")).read
    assert read(run) is None
    run.spans = [("train.neighbours.encode", 0, 3_000_000_000),
                 ("train.neighbours", 0, 5_000_000_000),
                 ("train.prep.pack", 0, 9_000_000_000)]
    assert read(run) == 5.0


def test_a_program_that_diverges_ends_the_run(monkeypatch):
    """Trained with the steps stated for 1–5 ratings on the 0–100 scale,
    `fit` reads a non-finite RMSE after its first epoch; the cell stops
    there with a non-zero exit and prints no result."""
    from repro.core import sgd
    monkeypatch.setattr(sgd.Hyper, "for_rating_scale", lambda self, s: self)

    def cut(cfg, traffic):
        cfg = copy.deepcopy(cfg)
        cfg["data"].update(M=1500, N=3000, nnz=60000)
        return cfg, traffic

    with pytest.raises(SystemExit, match="does not train"):
        harness.execute(SPARSE, 7, 3.0, False, 0.0, jax.devices(),
                        overrides=cut)
