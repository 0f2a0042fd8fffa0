"""Online learning (paper Alg. 4): new users/items arrive, the model
updates incrementally — no retraining of existing parameters.

    PYTHONPATH=src python examples/online_learning.py
"""
import dataclasses
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core import model, online
from repro.core.sgd import Hyper
from repro.core.simlsh import SimLSHConfig
from repro.data import synthetic as syn
from repro.data.sparse import from_coo, train_test_split
from repro.train.trainer import FitConfig, fit


def main():
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=3000, N=500,
                               nnz=150_000)
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    (tr_r, tr_c, tr_v), te = train_test_split(
        np.random.default_rng(0), rows, cols, vals)

    # "original" world = ids below the cut; the rest arrives later
    M0, N0 = spec.M - 100, spec.N - 16
    old = (tr_r < M0) & (tr_c < N0)
    lsh = SimLSHConfig(G=8, p=1, q=10, band_cap=16)
    cfg = FitConfig(F=32, K=16, epochs=6, method="simlsh", lsh=lsh,
                    eval_every=6)
    print("training on the original set...")
    res = fit((tr_r[old], tr_c[old], tr_v[old]), te, (M0, N0), cfg)

    st = online.OnlineState(
        params=res.params, S=res.S, JK=res.JK,
        sp=from_coo(tr_r[old], tr_c[old], tr_v[old], (M0, N0)),
        M=M0, N=N0, hash_key=res.hash_key)

    print(f"{int((~old).sum()):,} new interactions arrive "
          f"(new users ≥ {M0}, new items ≥ {N0})")
    t0 = time.time()
    st2 = online.online_update(
        st, tr_r[~old], tr_c[~old], tr_v[~old], lsh, Hyper(),
        jax.random.PRNGKey(0), M_new=spec.M, N_new=spec.N, K=16, epochs=3)
    t_online = time.time() - t0

    te_r, te_c, te_v = (np.asarray(a) for a in te)
    import jax.numpy as jnp
    rmse = float(model.rmse(st2.params, st2.sp, st2.JK,
                            jnp.asarray(te_r), jnp.asarray(te_c),
                            jnp.asarray(te_v)))
    print(f"online update: {t_online:.2f}s → rmse {rmse:.4f} "
          f"(retrain-from-scratch rmse for reference: run quickstart)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
