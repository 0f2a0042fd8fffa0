#!/usr/bin/env python3
"""Find the highest rate a serving configuration sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 4000,8000,...

Builds the cell's catalog, index and service once, then offers each rate
for ``--seconds`` with the cell's traffic mix and prints one line per
rate: the median and 99th percentile latency, the mean latency of the
first and of the last tenth of the requests, and the generator's worst
lag behind due time.  A rate is sustained when no backlog grows (the
last tenth waits at most ``GROWTH`` times as long as the first), the
tail stays near the median (p99 at most ``TAIL`` times p50), and the
generator keeps to its schedule (worst lag under ``LAG_MS``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GROWTH, TAIL, LAG_MS = 1.2, 2.0, 20.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import numpy as np

    import gen
    import harness
    import serve_cell
    from repro import compile_cache, obs
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    _, _, cfg, traffic = harness.load_cell(args.workload)
    run = harness.Run(cfg=cfg, traffic=traffic, seed=args.seed,
                      seconds=args.seconds, trace_on=False, t_start=T_START)
    arrays, svc = serve_cell.build(run, obs.Registry(enabled=True))
    M = arrays[0].shape[0]
    for rate in (float(r) for r in args.rates.split(",")):
        due, users = gen.arrivals(rate, args.seconds, M, traffic["zipf_a"],
                                  args.seed)
        _, got, _, _, lag, _ = serve_cell.window(svc, due, users,
                                                 cfg["serve"]["topn"])
        lat = (got - due) * 1e3
        k = max(1, lat.size // 10)
        row = {"rate": rate, "n": int(lat.size),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "first_tenth_ms": float(lat[:k].mean()),
               "last_tenth_ms": float(lat[-k:].mean()),
               "lag_worst_ms": float(lag.max() * 1e3)}
        row["sustained"] = bool(
            row["last_tenth_ms"] <= GROWTH * row["first_tenth_ms"]
            and row["p99_ms"] <= TAIL * row["p50_ms"]
            and row["lag_worst_ms"] < LAG_MS)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
