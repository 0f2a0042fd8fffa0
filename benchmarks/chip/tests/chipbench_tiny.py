"""Tiny versions of the benchmark's cells for its CPU tests: the same
configuration and traffic files, cut to sizes a test run holds, with the
Pallas kernels in interpret mode (the program's choice on a CPU)."""
import copy
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import harness  # noqa: E402

TRAIN, SERVE = "ml10m.culsh_train", "cat1m.walk_steady"
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# limits of the comparison at these sizes (the cells' own are set at
# their full sizes, on the chip)
LIMITS = {TRAIN: {"rmse_gap": 0.02, "late_gain_gap": 0.1,
                  "eval_gap": 1e-4},
          SERVE: {"score_gap": 1e-3, "miss_share": 0.5}}


def tiny(workload):
    def cut(cfg, traffic):
        cfg, traffic = copy.deepcopy(cfg), dict(traffic)
        if workload == TRAIN:
            cfg["data"].update(M=200, N=100, nnz=8000)
            cfg["reference"]["batch"] = 16
            traffic.update(nominal_epoch_s=1.0)
        else:
            cfg["catalog"].update(N=2000)
            cfg["serve"].update(micro_batch=32, C=128, band_budget=128,
                                n_popular=16, tile_b=8)
            traffic.update(rate_users_per_s=150, recall_sample=64,
                           trace_from_s=0.2, trace_s=0.3)
        cfg["limits"] = dict(LIMITS[workload])
        return cfg, traffic
    return cut


def small_train(cfg, traffic):
    """The training cell at a size where the late, decayed epochs that a
    lower precision cannot resolve show in a test run: 13 epochs for
    ``seconds=3``.  Its limits are set from CPU runs of the program, the
    control and the faults at this size."""
    cfg, traffic = tiny(TRAIN)(cfg, traffic)
    cfg["data"].update(M=1500, N=500, nnz=60000)
    cfg["reference"]["batch"] = 512
    cfg["limits"] = {"rmse_gap": 0.06, "late_gain_gap": 0.12,
                     "eval_gap": 1e-4}
    traffic.update(nominal_epoch_s=0.25)
    return cfg, traffic


def run(workload, seed=2**31 + 99, seconds=3.0, trace=False):
    """One tiny run through the harness → the parsed result line."""
    import jax
    line = json.dumps(harness.execute(
        workload, seed, seconds, trace, time.perf_counter(), jax.devices(),
        overrides=tiny(workload)))
    return json.loads(line)
