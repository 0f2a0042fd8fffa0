"""The ``train_sparse`` cell's comparison passes the program and fails
the control, each fault and random neighbour lists: small runs on the
CPU through the whole harness, with the sparse reference put in the
program's place (in bfloat16, or with a fault planted) on the lists the
program's own neighbour search gives."""
import copy
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny

SPARSE = "kdd11.culsh_train"
harness = tiny.harness


@pytest.fixture(autouse=True)
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


def small(cfg, traffic):
    """13 epochs for ``seconds=3`` at a size where the late, decayed
    epochs show; the limits are set from CPU runs of the program, the
    control and the faults at this size and seed."""
    cfg, traffic = copy.deepcopy(cfg), dict(traffic)
    cfg["data"].update(M=1500, N=500, nnz=60000)
    cfg["reference"].update(batch=64, epochs=13)
    cfg["limits"] = {"rmse_gap": 0.015, "late_gain_gap": 0.5,
                     "eval_gap": 1e-4, "rmse_lag": 0.015, "nb_sim_gap": 0.6}
    traffic.update(nominal_epoch_s=0.25)
    return cfg, traffic


def sparse_catalog(method):
    """A catalog forty times sparser than at `small`, where simLSH's lists
    hold far more similar items than random ones do."""
    def cut(cfg, traffic):
        cfg, traffic = copy.deepcopy(cfg), dict(traffic)
        cfg["data"].update(M=2000, N=20000, nnz=60000)
        cfg["fit"]["method"] = method
        cfg["limits"]["nb_sim_gap"] = 0.85
        traffic.update(nominal_epoch_s=1.0)
        return cfg, traffic
    return cut


def reference_fit(cfg, dtype="float32", fault=""):
    """A stand-in for `repro.train.trainer.fit`: the sparse reference,
    trained from the initial state in ``fit``'s checkpoint on the lists
    `trainer.build_neighbours` gives for ``fit``'s seed."""
    import gen
    from repro.core.model import Params
    from repro.data.sparse import from_coo
    from repro.train import checkpoint as ckpt
    from repro.train.trainer import build_neighbours
    ref = harness.reference(cfg)

    def fit(train, test, shape, fc, log=None, registry=None):
        M, N = shape
        z = lambda *s: jnp.zeros(s, jnp.float32)
        like = Params(U=z(M, fc.F), V=z(N, fc.F), b=z(M), bh=z(N),
                      W=z(N, fc.K), C=z(N, fc.K), mu=z())
        p0, _ = ckpt.restore(fc.ckpt_dir, like)
        p0 = {k: getattr(p0, k) for k in ("U", "V", "b", "bh", "W", "C",
                                          "mu")}
        k_nb = jax.random.split(jax.random.PRNGKey(fc.seed), 3)[0]
        JK = build_neighbours(from_coo(*train, shape), fc, k_nb)[0]
        hist = []

        def on_epoch(t, r):
            hist.append((t, 0.0, r))
            if log:
                log(f"epoch {t:3d}  rmse={r:.4f}")

        p, _, _ = ref.train(
            p0, train, test, M, N, fc.K, fc.epochs, cfg["hyper"],
            jax.random.fold_in(gen.key_of(fc.seed, 6), 1),
            batch=cfg["reference"]["batch"], JK=JK, dtype=jnp.dtype(dtype),
            fault=fault, on_epoch=on_epoch)
        params = Params(**{k: v.astype(jnp.float32) for k, v in p.items()})
        return SimpleNamespace(params=params, JK=JK, history=hist)

    return fit


def test_program_passes():
    r = harness.execute(SPARSE, 7, 3.0, False, 0.0, jax.devices(),
                        overrides=small)
    assert r["attempted"] == 13 and r["failed"] == 0
    assert set(r["checks"]) == {"rmse_gap", "late_gain_gap", "eval_gap",
                                "rmse_lag", "nb_sim_gap"}
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("variant,fails_by", [
    (dict(dtype="bfloat16"), "late_gain_gap"),
    (dict(fault="half_batch"), "rmse_gap"),
    (dict(fault="frozen"), "rmse_gap"),
])
def test_control_and_faults_fail(monkeypatch, variant, fails_by):
    from repro.train import trainer
    _, _, cfg, traffic = harness.load_cell(SPARSE)
    monkeypatch.setattr(trainer, "fit",
                        reference_fit(small(cfg, traffic)[0], **variant))
    r = harness.execute(SPARSE, 7, 3.0, False, 0.0, jax.devices(),
                        overrides=small)
    assert r["correct"] is False
    c = r["checks"][fails_by]
    assert c["value"] > c["limit"], r["checks"]


@pytest.mark.parametrize("method", ["simlsh", "rand"])
def test_random_lists_fail_the_neighbour_check(method):
    r = harness.execute(SPARSE, 7, 3.0, False, 0.0, jax.devices(),
                        overrides=sparse_catalog(method))
    c = r["checks"]["nb_sim_gap"]
    assert (c["value"] > c["limit"]) is (method == "rand"), c
    if method == "rand":
        assert r["correct"] is False
