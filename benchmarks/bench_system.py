"""System-level benchmarks: Pallas kernels, roofline table.

* kernels: interpret-mode µs/call vs the pure-jnp oracle (NOTE: interpret
  mode is a correctness harness — TPU wall-clock is the dry-run's domain);
* roofline: re-emit the dry-run sweep's per-cell terms as CSV (reads
  reports/dryrun/16x16; run `python -m repro.launch.dryrun --all --roofline`
  first for the full table).
"""
from __future__ import annotations

import glob
import json

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed


def bench_kernels():
    from repro.kernels.mf_sgd.kernel import mf_sgd_step
    from repro.kernels.mf_sgd.ref import mf_sgd_step_ref
    from repro.kernels.neighbor_predict.kernel import neighbor_predict
    from repro.kernels.neighbor_predict.ref import neighbor_predict_ref
    from repro.kernels.simlsh_encode.kernel import simlsh_encode
    from repro.kernels.simlsh_encode.ref import simlsh_encode_ref
    rng = np.random.default_rng(0)

    N, deg, bits = 512, 128, 24
    psi = jnp.asarray(rng.normal(size=(N, deg)).astype(np.float32))
    phi = jnp.asarray(rng.choice([-1., 1.], (N, deg, bits)).astype(np.float32))
    _, t_int = timed(simlsh_encode, psi, phi, repeat=3, interpret=True)
    _, t_ref = timed(simlsh_encode_ref, psi, phi, repeat=3)
    emit("kernel.simlsh_encode.interpret", t_int,
         f"ref_us={t_ref*1e6:.0f};bytes={psi.nbytes + phi.nbytes}")

    B, F, K = 4096, 32, 32
    a = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    args = (a(B, F), a(B, F), a(B, K), a(B, K), a(B, K), a(B, K),
            a(B), a(B), a(B))
    _, t_int = timed(neighbor_predict, *args, repeat=3, interpret=True)
    _, t_ref = timed(neighbor_predict_ref, *args, repeat=3)
    emit("kernel.neighbor_predict.interpret", t_int, f"ref_us={t_ref*1e6:.0f}")

    u, v, r = a(B, F), a(B, F), a(B)
    valid = jnp.ones((B,), jnp.float32)
    _, t_int = timed(mf_sgd_step, u, v, r, valid, 0.02, 0.02, 0.01, 0.01,
                     repeat=3, interpret=True)
    _, t_ref = timed(mf_sgd_step_ref, u, v, r, valid, 0.02, 0.02, 0.01, 0.01,
                     repeat=3)
    emit("kernel.mf_sgd.interpret", t_int, f"ref_us={t_ref*1e6:.0f}")


def bench_roofline():
    files = sorted(glob.glob("reports/dryrun/16x16/*.json"))
    if not files:
        emit("roofline", 0.0, "no dry-run artifacts; run repro.launch.dryrun")
        return
    for f in files:
        rec = json.load(open(f))
        if rec.get("skipped") or "roofline" not in rec:
            continue
        r = rec["roofline"]
        emit(f"roofline.{rec['arch']}.{rec['shape']}", r["t_step"],
             f"bound={r['bound']};t_comp={r['t_compute']:.4g};"
             f"t_mem={r['t_memory']:.4g};t_coll={r['t_collective']:.4g};"
             f"useful={r['useful_ratio']:.3f}")


def run_all():
    bench_kernels()
    bench_roofline()
