"""Share of its roofline that the `culsh_sgd_step` kernel reaches.

Each call's work is counted from its operand shapes in the trace: the
batch-minor tiles row ``[F+1, B]``, col ``[F+2K+1, B]``, the three
neighbour planes ``[K, B]``, r and valid ``[1, B]`` and the 13
hyper-parameters in, the two updated planes out.  Its HBM bytes are those
of the operands and results the compiler left in HBM; inside the epoch's
scans it stages them all in VMEM (layout ``S(1)``) with copies of its
own, so the kernel itself moves no HBM bytes and its FLOPs bound it.  The
least time of a call is the larger of its FLOPs over the peak FLOP/s and
its HBM bytes over the HBM bandwidth; the share is the least time of all
calls over their measured time.
"""
import math

import xplane


def flops_per_sample(F: int, K: int) -> int:
    """Eq. (1) forward and the six Eq. (5) updates, per sample: the
    K-wide neighbour terms (residuals 3K, counts 2K, the two weighted
    sums 4K, w update 7K, c update 6K), the F-wide dot (2F) and u, v
    updates (6F each), and ~25 scalar operations."""
    return 14 * F + 22 * K + 25


def call_cost(text: str) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call, from its HLO text."""
    _, ops = xplane.operand_shapes(text)
    row, rnb = ops[0][1], ops[2][1]
    F, K, B = row[0] - 1, rnb[0], row[1]
    res, ops = xplane.operand_shapes(text, hbm_only=True)
    elems = sum(math.prod(d) for _, d in ops) + sum(math.prod(d)
                                                     for _, d in res)
    return float(flops_per_sample(F, K) * B), 4.0 * elems


def share(calls, peak) -> float | None:
    if not calls:
        return None
    least = sum(max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b in (call_cost(t) for t, _ in calls))
    return 100.0 * least / sum(s for _, s in calls)


def read(run):
    if run.trace is None:
        return None
    return share(run.trace.kernel_calls("culsh_sgd_step"), run.peak)
