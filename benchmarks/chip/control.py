#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting the limits
of a cell's correctness comparison.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds <s>] [--variants control,...]

Each variant puts something broken in the program's place and runs the
cell through the harness, as the benchmark's own runs do; one JSON line
per (seed, variant) gives ``correct`` and every compared number with its
limit.  A sound limit leaves each of them ``correct: false``.

* training cells — ``control``: the plain reference trained in bfloat16
  (the precision below the float32 the configuration states) and
  evaluated in float32, in `fit`'s place; ``half_batch``: the float32
  reference with every other rating of a block left out; ``frozen``: the
  reference returning its state unchanged from each epoch.
* serving cells — ``control``: every flush answered by the reference's
  exact top-N scored in bfloat16; ``answer_altered``: the program's
  answers with their last item replaced by the next id;
  ``half_batch``: the first half of a flush's lists given to the whole
  flush; ``retrieval_altered``: the walk's candidates moved half the
  catalog away.

The benchmark's own runs never run this; its tests run it at tiny sizes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def reference_fit(cfg: dict, dtype: str = "float32", fault: str = ""):
    """A stand-in for `repro.train.trainer.fit`: the cell's plain
    reference, trained from the initial state in ``fit``'s checkpoint."""
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    import gen
    import harness
    from repro.core.model import Params
    from repro.train import checkpoint as ckpt
    ref = harness.reference(cfg)

    def fit(train, test, shape, fc, log=None, registry=None):
        M, N = shape
        z = lambda *s: jnp.zeros(s, jnp.float32)
        like = Params(U=z(M, fc.F), V=z(N, fc.F), b=z(M), bh=z(N),
                      W=z(N, fc.K), C=z(N, fc.K), mu=z())
        p0, _ = ckpt.restore(fc.ckpt_dir, like)
        p0 = {k: getattr(p0, k) for k in ("U", "V", "b", "bh", "W", "C",
                                          "mu")}
        hist = []

        def on_epoch(t, r):
            hist.append((t, 0.0, r))
            if log:
                log(f"epoch {t:3d}  rmse={r:.4f}")

        p, _, JK = ref.train(
            p0, train, test, M, N, fc.K, fc.epochs, cfg["hyper"],
            jax.random.fold_in(gen.key_of(fc.seed, 6), 1),
            batch=cfg["reference"]["batch"], dtype=jnp.dtype(dtype),
            fault=fault, on_epoch=on_epoch)
        params = Params(**{k: v.astype(jnp.float32) for k, v in p.items()})
        return SimpleNamespace(params=params, JK=JK, history=hist)

    return fit


def train_variants(cfg):
    from repro.train import trainer
    return {
        "control": patched(trainer, "fit", reference_fit(cfg, "bfloat16")),
        "half_batch": patched(trainer, "fit",
                              reference_fit(cfg, fault="half_batch")),
        "frozen": patched(trainer, "fit", reference_fit(cfg, fault="frozen")),
    }


def serve_variants(cfg):
    import jax
    import jax.numpy as jnp

    from repro.kernels.lsh_retrieve import ops
    from repro.serve.service import RecsysService
    topn = cfg["serve"]["topn"]
    recommend = RecsysService._recommend

    @jax.jit
    def low(U, V, bh, mu, b, users):
        c = lambda a: a.astype(jnp.bfloat16)
        s = c(mu) + c(b)[users][:, None] + c(bh)[None, :] + jnp.dot(
            c(U)[users], c(V).T, preferred_element_type=jnp.bfloat16)
        top, items = jax.lax.top_k(s, topn)
        return top.astype(jnp.float32), items.astype(jnp.int32)

    def control(self, user_ids):
        p = self.params
        return low(p.U, p.V, p.bh, p.mu, p.b, user_ids)

    def altered(self, user_ids):
        s, it = recommend(self, user_ids)
        return s, it.at[:, -1].set((it[:, -1] + 1) % self.planes.n_items)

    def half(self, user_ids):
        s, it = recommend(self, user_ids)
        h = it.shape[0] // 2
        return (jnp.concatenate([s[:h], s[:it.shape[0] - h]]),
                jnp.concatenate([it[:h], it[:it.shape[0] - h]]))

    retrieve = ops.retrieve_candidates

    def shifted(index, sp, user_ids, **k):
        cand = retrieve(index, sp, user_ids, **k)
        return jnp.where((cand >= 0) & (cand < sp.N),
                         (cand + sp.N // 2) % sp.N, cand)

    return {
        "control": patched(RecsysService, "_recommend", control),
        "answer_altered": patched(RecsysService, "_recommend", altered),
        "half_batch": patched(RecsysService, "_recommend", half),
        "retrieval_altered": patched(ops, "retrieve_candidates", shifted),
    }


def readings(workload, seed, seconds, devices, names=None, overrides=None):
    """[{variant, correct, checks}] for each variant of ``workload``."""
    import jax

    import harness
    _, _, cfg, traffic = harness.load_cell(workload)
    if overrides:
        cfg, traffic = overrides(cfg, traffic)
    variants = (train_variants if traffic["kind"] == "train"
                else serve_variants)(cfg)
    out = []
    for name, patch in variants.items():
        if names and name not in names:
            continue
        t0 = time.perf_counter()
        jax.clear_caches()
        with patch:
            r = harness.execute(workload, seed, seconds, False,
                                time.perf_counter(), devices,
                                overrides=overrides)
        jax.clear_caches()
        out.append(dict(variant=name, seed=seed, correct=r["correct"],
                        checks=r["checks"], failed=r["failed"],
                        seconds_taken=time.perf_counter() - t0))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax

    import harness
    from repro import compile_cache
    spec = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    seconds = args.seconds or spec["run_seconds"]
    names = [v for v in args.variants.split(",") if v]
    for seed in (int(s) for s in args.seeds.split(",")):
        for r in readings(args.workload, seed, seconds, devices, names):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
