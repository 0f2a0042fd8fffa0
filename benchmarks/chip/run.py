#!/usr/bin/env python3
"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees at least the
chips the cell asks for; without a TPU it exits non-zero before any work.
The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` close standard error, each beside its
limit.  With ``--trace 1`` the metrics are the cell's per-layer ones,
read from a profiler trace of part of the window, written under the
temporary directory and deleted once read.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    _, cell, _, _ = harness.load_cell(args.workload)
    import jax
    from repro import compile_cache
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {d0.platform} device(s)", file=sys.stderr)
        return 2
    harness.peaks_for(d0.device_kind)
    compile_cache.enable()
    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
