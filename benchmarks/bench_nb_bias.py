"""Microbenchmark of the neighbour-baseline lookup b̂[J^K[j]] on a TPU.

Every CULSH-MF step reads b̂ at its samples' K neighbour ids.  This times
the ways of doing that read inside a scan shaped like the training epoch:
the carry is the ``[N, F+2K+1]`` col plane, each step looks b̂ up at a
fresh ``[K, B]`` (or ``[B, K]``) id plane and scatters a small update back
into the b̂ column, so that the table changes every step and no candidate
can hoist its per-step work out of the loop.  A step with no lookup is the
floor each candidate's time is taken from.

Candidates:

* ``gather2d``   — ``col[nb, F+2K]``, the gather the epoch used to run;
* ``gather1d``   — ``col[:, F+2K][nb]``, a gather from the 1-D b̂ vector;
* ``onehot_xla`` — `ops.neighbour_baselines`' vectorised path: a one-hot
  of ``id // 128`` against the byte planes of b̂ on the MXU, then lane
  ``id % 128`` by an iota compare;
* ``onehot_pallas_<tn>`` — the same arithmetic as one Pallas kernel, ids
  on lanes, ``tn`` lookups a grid step.

Each candidate's bits are compared with ``gather2d`` on the chip, over
b̂ values from 1e-30 to 1e+3 with both signs, zeros, and the ids 0 and
N−1.  Results: one JSON line per measurement on stdout and in ``--out``.

    python3 benchmarks/bench_nb_bias.py [--steps 500] [--repeats 5]
        [--out reports/nb_bias_bench.jsonl]

It refuses to run without a TPU: a CPU time is not a device time.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.kernels.mf_sgd import ops  # noqa: E402

F = K = 32
BH = F + 2 * K                 # the b̂ column of the col plane


def gather2d(col, nb):
    return col[nb, BH]


def gather1d(col, nb):
    return col[:, BH][nb]


def onehot_xla(col, nb):
    return ops._nb_bias_onehot(col[:, BH], nb)


def _onehot_kernel(ids_ref, tab_ref, out_ref):
    ids = ids_ref[...]                                   # [1, tn]
    tn = ids.shape[1]
    h2 = tab_ref.shape[1]
    hi, lo = ids >> 7, ids & 127
    r = lax.broadcasted_iota(jnp.int32, (h2, tn), 0)
    r = jnp.where(r >= h2 // 2, r - h2 // 2, r)
    oh = (r == hi).astype(jnp.bfloat16)                  # [2H, tn]
    rows = jnp.dot(tab_ref[...], oh, preferred_element_type=jnp.float32)
    s = lax.broadcasted_iota(jnp.int32, (128, tn), 0) == lo
    lo16 = jnp.sum(jnp.where(s, rows[:128], 0.0), 0, keepdims=True)
    hi16 = jnp.sum(jnp.where(s, rows[128:], 0.0), 0, keepdims=True)
    out_ref[...] = lax.bitcast_convert_type(
        lo16.astype(jnp.int32) | (hi16.astype(jnp.int32) << 16),
        jnp.float32)


def onehot_pallas(col, nb, tn):
    bh = col[:, BH]
    N = bh.shape[0]
    H = -(-N // 1024) * 8
    bits = lax.bitcast_convert_type(jnp.pad(bh, (0, H * 128 - N)),
                                    jnp.int32).reshape(H, 128)
    byte = lambda k: ((bits >> (8 * k)) & 0xFF).astype(jnp.float32)
    tab = jnp.concatenate(
        [jnp.concatenate([byte(0), byte(1) * 256.0], 0),
         jnp.concatenate([byte(2), byte(3) * 256.0], 0)],
        1).T.astype(jnp.bfloat16)                        # [256, 2H]
    ids = nb.reshape(1, -1)
    n = ids.shape[1]
    t = min(tn, n)
    ids = jnp.pad(ids, ((0, 0), (0, (-n) % t)))
    out = pl.pallas_call(
        _onehot_kernel, grid=(ids.shape[1] // t,),
        in_specs=[pl.BlockSpec((1, t), lambda i: (0, i)),
                  pl.BlockSpec(tab.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, t), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(ids.shape, jnp.float32),
    )(ids, tab)
    return out[0, :n].reshape(nb.shape)


def none(col, nb):
    return nb.astype(jnp.float32)


def candidates(N: int) -> dict:
    c = dict(none=none, gather2d=gather2d, gather1d=gather1d,
             onehot_xla=onehot_xla)
    for tn in (512, 2048, 8192):
        # keep the kernel's one-hot block at or under 4 MiB of VMEM
        if 2 * (-(-N // 1024) * 8) * tn * 2 <= 4 << 20:
            c[f"onehot_pallas_{tn}"] = (
                lambda col, nb, tn=tn: onehot_pallas(col, nb, tn))
    return c


def epoch_like(fn, steps: int):
    """A jitted scan of ``steps`` lookups, each followed by a 512-row
    scatter into the b̂ column of the carried plane."""
    @jax.jit
    def run(col, nbs, js):
        def body(c, x):
            nb, j = x
            v = fn(c, nb)
            upd = jnp.sum(v.reshape(-1, j.shape[0]), 0) * 1e-9
            return c.at[j, BH].add(upd), None
        return lax.scan(body, col, (nbs, js))[0]
    return run


def timed(run, args, repeats: int) -> float:
    jax.block_until_ready(run(*args))                # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def table(rng, N: int) -> np.ndarray:
    col = rng.standard_normal((N, BH + 1)).astype(np.float32)
    mag = 10.0 ** rng.uniform(-30, 3, N)
    col[:, BH] = (rng.choice([-1.0, 0.0, 1.0], N) * mag).astype(np.float32)
    return col


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sizes", default="10677,32768,65536,131072,262144")
    ap.add_argument("--out", default=str(ROOT / "reports"
                                         / "nb_bias_bench.jsonl"))
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refusing: no TPU ({dev.platform})", file=sys.stderr)
        return 2
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    with open(a.out, "w") as sink:
        rng = np.random.default_rng(0)
        B = 512
        # shapes: the w512 and w64 kernel tiers ([K, B]) and the leftovers
        # tier's [B, K]
        shapes = {"w512": (K, 512), "w64": (K, 64), "leftovers": (512, K)}
        for N in (int(s) for s in a.sizes.split(",")):
            col = table(rng, N)
            col[0, BH], col[N - 1, BH] = -1.5e-30, 7.25e2
            colj = jnp.asarray(col)
            for sname, shape in shapes.items():
                if N != 10677 and sname == "leftovers":
                    continue
                nbs = rng.integers(0, N, (a.steps,) + shape).astype(np.int32)
                nbs[0].flat[:4] = [0, N - 1, 0, N - 1]
                js = np.stack([rng.choice(N, B, replace=False)
                               for _ in range(a.steps)]).astype(np.int32)
                nbs, js = jnp.asarray(nbs), jnp.asarray(js)
                want = lax.bitcast_convert_type(gather2d(colj, nbs[0]),
                                                jnp.int32)
                t_none = None
                for name, fn in candidates(N).items():
                    got = jax.jit(fn)(colj, nbs[0])
                    exact = bool(jnp.array_equal(
                        lax.bitcast_convert_type(got, jnp.int32), want))
                    t = timed(epoch_like(fn, a.steps), (colj, nbs, js),
                              a.repeats)
                    if name == "none":
                        t_none = t
                    rec = dict(N=N, shape=sname, lookups=int(np.prod(shape)),
                               candidate=name,
                               exact=exact if name != "none" else None,
                               step_us=1e6 * t / a.steps,
                               lookup_us=1e6 * (t - t_none) / a.steps,
                               device=dev.device_kind)
                    line = json.dumps(rec)
                    print(line, flush=True)
                    sink.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
