"""The whole training epoch's share of the chip's peak: the least time
the chip needs for the epoch's required work over the device time of the
traced epoch programs (``jit_train_epoch_scheduled``).

Required work per training rating: the `culsh_sgd_step` arithmetic, and
the bytes of reading and writing its row-plane row (F+1) and col-plane
row (F+2K+1), reading its K neighbour ids, ratings, explicit flags and
neighbour biases, and its i, j and r — all float32 or int32.
"""


def per_sample(F: int, K: int) -> tuple[int, int]:
    flops = 14 * F + 22 * K + 25
    words = 2 * (F + 1) + 2 * (F + 2 * K + 1) + 4 * K + 3
    return flops, 4 * words


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.module_calls("jit_train_epoch_scheduled")
    if not calls:
        return None
    fit = run.cfg["fit"]
    f, b = per_sample(fit["F"], fit["K"])
    n = len(calls) * run.facts["n_train"]
    least = max(n * f / run.peak["flops_per_s"],
                n * b / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(s for _, s in calls)
