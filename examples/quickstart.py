"""Quickstart: train LSH-MF (the paper's model) on synthetic sparse data.

    PYTHONPATH=src python examples/quickstart.py

Builds a MovieLens-like sparse matrix, finds Top-K item neighbours with
simLSH (no GSM!), trains the nonlinear neighbourhood MF with the fused
Eq.(5) SGD, and prints test RMSE per epoch — compare `method="rand"` or
`method="gsm"` to reproduce the paper's Fig. 7 orderings.
"""
import dataclasses

import numpy as np

from repro import compile_cache
from repro.core.simlsh import SimLSHConfig
from repro.data import synthetic as syn
from repro.data.sparse import train_test_split
from repro.train.trainer import FitConfig, fit


def main():
    spec = dataclasses.replace(syn.MOVIELENS_LIKE, M=3000, N=500,
                               nnz=150_000)
    rows, cols, vals, _ = syn.generate(spec, seed=0)
    tr, te = train_test_split(np.random.default_rng(0), rows, cols, vals)

    cfg = FitConfig(
        F=32, K=16, epochs=8, batch=4096,
        method="simlsh",                      # try: gsm | rand | rp_cos | minhash | none
        lsh=SimLSHConfig(G=8, p=1, q=20, band_cap=16, psi_pow=2.0),
    )
    res = fit(tr, te, (spec.M, spec.N), cfg, log=print)
    print(f"neighbour search took {res.neighbour_seconds:.2f}s "
          f"(GSM would be O(N²) = {spec.N ** 2:,} similarities)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
