"""The comparison that decides ``correct`` fails the control and each
fault the cells can have.  Tiny runs on the CPU drive the whole harness
with the timed path broken underneath: faults planted in the program,
and `control`'s variants — the plain reference in bfloat16 put in the
program's place, and the faults it plants there."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny


@pytest.fixture(autouse=True)
def fresh_traces():
    jax.clear_caches()          # a patched function must be traced anew
    yield
    jax.clear_caches()


def _fails(r, *names):
    assert r["correct"] is False
    assert any(r["checks"][n]["value"] > r["checks"][n]["limit"]
               for n in names), r["checks"]


def test_training_step_returning_its_state(monkeypatch):
    from repro.core import sgd
    static = ("mf_only", "bce", "use_kernels", "impl", "interpret", "tile_b",
              "mesh")

    def unchanged(pp, sd, sched, key, epoch, hp, *, shd=None, mf_only=False,
                  bce=False, use_kernels=False, impl="ref", interpret=False,
                  tile_b=256, mesh=None):
        return pp

    monkeypatch.setattr(sgd, "train_epoch_scheduled",
                        jax.jit(unchanged, static_argnames=static))
    _fails(tiny.run(tiny.TRAIN), "rmse_gap")


def test_training_half_of_each_batch_left_out(monkeypatch):
    from repro.core import sgd
    orig = sgd.apply_culsh_sgd

    def half(pp, bt, *a, **k):
        keep = (jnp.arange(bt.valid.shape[0]) % 2 == 0).astype(jnp.float32)
        return orig(pp, dataclasses.replace(bt, valid=bt.valid * keep),
                    *a, **k)

    monkeypatch.setattr(sgd, "apply_culsh_sgd", half)
    _fails(tiny.run(tiny.TRAIN), "rmse_gap")


def test_training_answer_altered_where_produced(monkeypatch):
    from repro.core import model
    orig = model.rmse_cached
    monkeypatch.setattr(model, "rmse_cached",
                        lambda *a, **k: orig(*a, **k) * 1.01)
    _fails(tiny.run(tiny.TRAIN), "eval_gap")


def _patch_flush(monkeypatch, alter):
    from repro.serve.service import RecsysService
    orig = RecsysService._recommend

    def broken(self, user_ids):
        scores, items = orig(self, user_ids)
        return alter(scores, items, self.planes.n_items)

    monkeypatch.setattr(RecsysService, "_recommend", broken)


def test_serving_answer_altered_where_produced(monkeypatch):
    _patch_flush(monkeypatch, lambda s, it, n: (
        s, it.at[:, -1].set((it[:, -1] + 1) % n)))
    _fails(tiny.run(tiny.SERVE, seconds=2.0), "score_gap")


def test_serving_half_of_each_batch_left_out(monkeypatch):
    def half(s, it, n):
        h = it.shape[0] // 2
        return (jnp.concatenate([s[:h], s[:it.shape[0] - h]]),
                jnp.concatenate([it[:h], it[:it.shape[0] - h]]))
    _patch_flush(monkeypatch, half)
    _fails(tiny.run(tiny.SERVE, seconds=2.0), "score_gap")


def test_serving_retrieval_altered_where_produced(monkeypatch):
    from repro.kernels.lsh_retrieve import ops
    orig = ops.retrieve_candidates

    def shifted(index, sp, user_ids, **k):
        cand = orig(index, sp, user_ids, **k)
        # half the catalog away: another planted group
        return jnp.where((cand >= 0) & (cand < sp.N),
                         (cand + sp.N // 2) % sp.N,
                         cand)

    monkeypatch.setattr(ops, "retrieve_candidates", shifted)
    _fails(tiny.run(tiny.SERVE, seconds=2.0), "miss_share")


@pytest.mark.parametrize("variant", ["control", "half_batch", "frozen"])
def test_control_fails_training_comparison(variant):
    import control
    r, = control.readings(tiny.TRAIN, 7, 3.0, jax.devices(), [variant],
                          overrides=tiny.small_train)
    assert r["correct"] is False, r


def test_program_passes_at_the_control_size():
    r = tiny.harness.execute(tiny.TRAIN, 7, 3.0, False, 0.0, jax.devices(),
                             overrides=tiny.small_train)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("variant", ["control", "answer_altered",
                                     "half_batch", "retrieval_altered"])
def test_control_fails_serving_comparison(variant):
    import control
    r, = control.readings(tiny.SERVE, 7, 2.0, jax.devices(), [variant],
                          overrides=tiny.tiny(tiny.SERVE))
    assert r["correct"] is False, r
