"""`fit`'s step sizes for the scale of its ratings (`sgd.Hyper`,
`sgd.rating_scale`): the stated steps on 1–5 data, steps that follow the
path of the 1–5 problem on wider scales."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import model, sgd
from repro.data.sparse import conflict_free_schedule

S = 32


@pytest.mark.parametrize("lo,hi,s", [(1.0, 5.0, 1), (1.0, 4.2, 1),
                                     (0.5, 5.0, 2), (0.0, 4.0, 1),
                                     (0.0, 4.01, 2), (0.0, 100.0, 32),
                                     (0.0018, 99.99, 32), (3.0, 3.0, 1),
                                     (0.0, 1.0, 1), (-128.0, 128.0, 64)])
def test_rating_scale(lo, hi, s):
    vals = jnp.asarray([hi, lo, (lo + hi) / 2], jnp.float32)
    assert sgd.rating_scale(vals) == s


def test_steps_unchanged_on_one_to_five(tiny_dataset):
    """On 1–5 data s = 1 and every step, λ and β is the stated one, bit
    for bit."""
    vals = tiny_dataset[3]
    assert 1.0 <= vals.min() and vals.max() <= 5.0
    hp = sgd.Hyper()
    got = hp.for_rating_scale(sgd.rating_scale(vals))
    f32 = lambda h: {k: np.float32(v).view(np.int32)
                     for k, v in dataclasses.asdict(h).items()}
    assert f32(got) == f32(hp)


def test_steps_scaled_exactly_at_32():
    hp = sgd.Hyper()
    got = dataclasses.asdict(hp.for_rating_scale(S))
    want = dataclasses.asdict(hp)
    scale = dict(a_u=1 / S, a_v=1 / S, l_u=S, l_v=S, a_w=S ** -2, l_w=S ** 2)
    for k, v in want.items():
        assert np.float32(got[k]) == np.float32(v) * np.float32(
            scale.get(k, 1.0)), k
    for a, lam in (("a_u", "l_u"), ("a_v", "l_v"), ("a_w", "l_w")):
        assert got[a] * got[lam] == want[a] * want[lam]


def _mapped(p: model.Params, s: int) -> model.Params:
    """The parameters of the r / s problem mapped onto the problem on r:
    U, V × √s; μ, b, b̂, C × s; W unchanged."""
    r = math.sqrt(s)
    return dataclasses.replace(p, U=p.U * r, V=p.V * r, mu=p.mu * s,
                               b=p.b * s, bh=p.bh * s, C=p.C * s)


@pytest.mark.parametrize("engine", ["general", "scheduled"])
def test_epoch_on_r_is_s_times_the_epoch_on_r_over_s(tiny_sparse, engine):
    """One epoch from the mapped parameters on ratings r, with the mapped
    steps, lands on the mapped result of the epoch on r / s with the
    stated steps: predictions s times as large, to float32 rounding."""
    small = tiny_sparse
    big = dataclasses.replace(small, vals=small.vals * S)
    key = jax.random.PRNGKey(2)
    JK = jax.random.randint(jax.random.PRNGKey(1), (small.N, 4), 0, small.N)
    p0 = model.init_from_data(jax.random.PRNGKey(0), small, 8, 4)
    p0 = dataclasses.replace(
        p0, W=jnp.full_like(p0.W, 0.01), C=jnp.full_like(p0.C, 0.02))
    hp = sgd.Hyper()
    assert sgd.rating_scale(big.vals) == S
    ep = jnp.asarray(1)
    if engine == "general":
        run = lambda sp, p, h: sgd.train_epoch(p, sp, JK, key, ep, h,
                                               batch=128)
    else:
        sched = conflict_free_schedule(np.asarray(small.rows),
                                       np.asarray(small.cols), batch=128,
                                       M=small.M, N=small.N, seed=0)

        def run(sp, p, h):
            sd = model.build_scheduled_data(sp, JK, sched)
            pp = sgd.train_epoch_scheduled(model.pack_params(p), sd, sched,
                                           key, ep, h, use_kernels=True,
                                           impl="ref")
            return model.unpack_params(pp)

    copy = lambda p: jax.tree.map(jnp.copy, p)       # the epoch donates it
    v0 = np.asarray(p0.V) * math.sqrt(S)
    want = _mapped(run(small, copy(p0), hp), S)
    got = run(big, _mapped(copy(p0), S), hp.for_rating_scale(S))
    for k in ("U", "V", "b", "bh", "W", "C", "mu"):
        w, g = np.asarray(getattr(want, k)), np.asarray(getattr(got, k))
        np.testing.assert_allclose(g, w, rtol=2e-4,
                                   atol=2e-6 * np.abs(w).max(), err_msg=k)
    moved = np.abs(np.asarray(got.V) - v0).max()
    assert moved > 1e-3


@pytest.mark.parametrize("mapped", [True, False])
def test_fit_on_a_0_to_100_scale(monkeypatch, mapped):
    """A 1,500 × 3,000 × 60,000 fit on 0–100 ratings: with the steps `fit`
    derives, finite, its held-out RMSE falling every epoch, s = 32 in its
    counter and its log; with the steps stated for 1–5, not finite."""
    from repro import obs
    from repro.data import synthetic as syn
    from repro.data.sparse import train_test_split
    from repro.train.trainer import FitConfig, fit
    if not mapped:
        monkeypatch.setattr(sgd.Hyper, "for_rating_scale",
                            lambda self, s: self)
    spec = syn.DatasetSpec("wide", 1500, 3000, 60000, rmin=0.0, rmax=100.0)
    rows, cols, vals, _ = syn.generate(spec, seed=3)
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    reg = obs.Registry(enabled=True)
    logs = []
    res = fit(tr, te, (spec.M, spec.N), FitConfig(epochs=5), log=logs.append,
              registry=reg)
    hist = [r for _, _, r in res.history]
    assert reg.counter("train.rating_scale") == S
    assert any(m.startswith("rating scale: s = 32") for m in logs)
    if not mapped:
        assert not np.isfinite(hist[0])
        return
    assert np.all(np.isfinite(hist)), hist
    assert all(b < a for a, b in zip(hist, hist[1:])), hist
    assert np.all(np.isfinite(np.asarray(res.params.U)))
