"""The neighbour-baseline lookup b̂[J^K[j]] (`ops.neighbour_baselines`):
its one-hot path returns the gather's bits, and the path is chosen from
the static shapes alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgd
from repro.data.sparse import conflict_free_schedule
from repro.kernels.mf_sgd import ops

F = K = 32
BH = F + 2 * K


def _plane(N: int, seed: int) -> np.ndarray:
    """A col plane whose b̂ column spans 1e-30..1e+3 in magnitude, with
    both signs, zeros (+0 and −0), subnormals, infinities and a NaN."""
    rng = np.random.default_rng(seed)
    col = rng.standard_normal((N, BH + 1)).astype(np.float32)
    mag = 10.0 ** rng.uniform(-30, 3, N)
    bh = (rng.choice([-1.0, 0.0, 1.0], N) * mag).astype(np.float32)
    special = np.array([0.0, -0.0, 1e-30, -1e-30, 1e3, -1e3, 1e-45, -3e-39,
                        np.inf, -np.inf, np.nan], np.float32)
    bh[:min(N, special.size)] = special[:N]
    col[:, BH] = bh
    return col


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("N", [10677, 300, 128, 5])
@pytest.mark.parametrize("shape", [(32, 512), (512, 32), (32, 64), (7,)])
def test_onehot_lookup_bits_equal_gather(N, shape):
    """Bit for bit the gather ``col[nb, F+2K]``: N not a multiple of 128,
    the ids 0 and N−1, repeated ids, and every kind of b̂ value."""
    rng = np.random.default_rng(N + len(shape))
    col = jnp.asarray(_plane(N, N))
    nb = rng.integers(0, N, shape).astype(np.int32)
    flat = nb.reshape(-1)
    flat[:6] = [0, N - 1, 0, N - 1, N // 2, N // 2][:flat.size]
    nb = jnp.asarray(nb)
    want = jax.jit(lambda c, i: c[i, BH])(col, nb)
    got = jax.jit(lambda c, i: ops._nb_bias_onehot(c[:, BH], i))(col, nb)
    assert got.shape == nb.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_onehot_lookup_every_id():
    """Every id of a catalog looks up its own b̂."""
    N = 1000
    bh = jnp.asarray(_plane(N, 7)[:, BH])
    ids = jnp.arange(N, dtype=jnp.int32)
    np.testing.assert_array_equal(_bits(ops._nb_bias_onehot(bh, ids)),
                                  _bits(bh))


@pytest.mark.parametrize("N,n,vectorised", [
    (10677, 32 * 512, True), (10677, 32 * 64, True),
    (123_361, 32 * 512, True), (123_362, 32 * 512, False),
    (87_381, 32 * 64, True), (87_382, 32 * 64, False),
    (1_000_000, 32 * 512, False), (10677, 7, False)])
def test_path_switches_at_crossover(N, n, vectorised):
    """Where N·(n + 1024) ≤ 2^17·n the lookup is traced with the one-hot
    matmul; past it, as one gather from the 1-D b̂ and no matmul."""
    assert ops.nb_bias_vectorised(N, n) is vectorised
    jaxpr = str(jax.make_jaxpr(ops.neighbour_baselines)(
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32)))
    assert ("dot_general" in jaxpr) is vectorised
    assert "gather" in jaxpr


def test_cpu_program_keeps_the_gather():
    """Compiled for a CPU, whose gather is fast, the lookup is a gather
    even below the crossover (the TPU side: `test_tpu_compile.py`)."""
    bh = jnp.asarray(_plane(10677, 3)[:, BH])
    nb = jnp.asarray(np.random.default_rng(3).integers(0, 10677, (32, 512)),
                     jnp.int32)
    assert ops.nb_bias_vectorised(10677, nb.size)
    txt = jax.jit(ops.neighbour_baselines).lower(bh, nb).compile().as_text()
    assert " gather(" in txt and " dot(" not in txt
    np.testing.assert_array_equal(_bits(ops.neighbour_baselines(bh, nb)),
                                  _bits(bh[nb]))


def test_lookup_steps_count_every_culsh_step(tiny_sparse):
    """`sgd.nb_bias_lookup_steps` counts the steps of every tier, the
    leftovers' included, whose width puts them on the one-hot path, and
    none for plain MF or for a CPU program."""
    sp = tiny_sparse
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=128, M=sp.M, N=sp.N, seed=0)
    steps = [s.shape[0] for s in sched.tier_starts]
    lo = sched.lo_starts.shape[0]
    assert len(steps) > 1 and lo > 0
    count = lambda N, **kw: sgd.nb_bias_lookup_steps(
        sched, N, 4, **dict(dict(mf_only=False, platform="tpu"), **kw))
    assert count(sp.N) == sum(steps) + lo
    assert count(sp.N, mf_only=True) == 0
    assert count(sp.N, platform="cpu") == 0
    # a catalog past the crossover of every width but the widest
    N = ops.ONEHOT_ITEMS_PER_LOOKUP * 4 * sched.widths[0] // (
        4 * sched.widths[0] + ops.ONEHOT_TABLE_LOOKUPS)
    assert not ops.nb_bias_vectorised(N, 4 * sched.widths[1])
    assert count(N) == steps[0] + lo
    assert count(N + 1) == 0


def test_fit_counts_lookup_steps(tiny_dataset, monkeypatch):
    """`fit` adds its program's one-hot steps to
    ``train.nb_bias_lookup_steps`` once per epoch: none on a CPU, every
    step of the epoch where it compiles for a TPU."""
    from repro import obs
    from repro.data.sparse import train_test_split
    from repro.train.trainer import FitConfig, fit
    spec, rows, cols, vals, _ = tiny_dataset
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    cfg = FitConfig(F=8, K=4, epochs=2, cf_batch=128, batch=128,
                    eval_every=0)
    for backend in ("cpu", "tpu"):
        # the plain-jnp step runs either way; only the count reads this
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        reg = obs.Registry(enabled=True)
        res = fit(tr, te, (spec.M, spec.N), cfg, registry=reg)
        stats = res.schedule_stats
        want = 0 if backend == "cpu" else 2 * (stats["nb_cf"]
                                              + stats["nb_lo"])
        assert "train.nb_bias_lookup_steps" in reg.counters
        assert reg.counter("train.nb_bias_lookup_steps") == want
        assert stats["nb_lo"] > 0


def test_gather_steps_count_every_step_past_the_crossover(tiny_sparse):
    """`sgd.nb_bias_gather_steps` counts each CULSH step whose width puts
    its lookup past the crossover (and every step of a CPU program), and
    none below it: with the lookup steps, every step of the epoch."""
    sp = tiny_sparse
    sched = conflict_free_schedule(np.asarray(sp.rows), np.asarray(sp.cols),
                                   batch=128, M=sp.M, N=sp.N, seed=0)
    steps = [s.shape[0] for s in sched.tier_starts]
    lo = sched.lo_starts.shape[0]
    total = sum(steps) + lo
    kw = dict(mf_only=False, platform="tpu")
    gather = lambda N, **k: sgd.nb_bias_gather_steps(sched, N, 4, **kw | k)
    lookup = lambda N: sgd.nb_bias_lookup_steps(sched, N, 4, **kw)
    N = ops.ONEHOT_ITEMS_PER_LOOKUP * 4 * sched.widths[0] // (
        4 * sched.widths[0] + ops.ONEHOT_TABLE_LOOKUPS)
    assert gather(sp.N) == 0
    assert gather(N) == sum(steps[1:])
    assert gather(N + 1) == total
    for n in (sp.N, N, N + 1, 624961):
        assert gather(n) + lookup(n) == total
    assert gather(sp.N, platform="cpu") == total
    assert gather(N + 1, mf_only=True) == 0
    # the published catalogs: ml10m's 10,677 items look b̂ up at every
    # width, the KDD-11 catalog's 624,961 gather at every width
    for w in (512, 256, 128, 64):
        assert ops.nb_bias_vectorised(10677, 32 * w)
        assert not ops.nb_bias_vectorised(624961, 32 * w)


def test_fit_counts_gather_steps_and_times_the_neighbour_search(
        tiny_dataset, monkeypatch):
    """`fit` adds its gather steps to ``train.nb_bias_gather_steps`` once
    per epoch — all of them on a CPU, none where a TPU program looks
    every b̂ up — and times simLSH's encoding and top-K in spans inside
    ``train.neighbours``."""
    from repro import obs
    from repro.data.sparse import train_test_split
    from repro.train.trainer import FitConfig, fit
    spec, rows, cols, vals, _ = tiny_dataset
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)
    cfg = FitConfig(F=8, K=4, epochs=2, cf_batch=128, batch=128,
                    eval_every=0)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        reg = obs.Registry(enabled=True)
        res = fit(tr, te, (spec.M, spec.N), cfg, registry=reg)
        stats = res.schedule_stats
        every = 2 * (stats["nb_cf"] + stats["nb_lo"])
        assert reg.counter("train.nb_bias_gather_steps") == (
            every if backend == "cpu" else 0)
        assert (reg.counter("train.nb_bias_gather_steps")
                + reg.counter("train.nb_bias_lookup_steps")) == every
    spans = {n: (t0, t0 + d) for n, t0, d, _, _ in reg.spans}
    outer = spans["train.neighbours"]
    for part in ("train.neighbours.encode", "train.neighbours.topk"):
        assert outer[0] <= spans[part][0] <= spans[part][1] <= outer[1]
