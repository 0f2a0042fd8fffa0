"""Share of its roofline that the `candidate_score_topn` kernel reaches.

Per call, from the operand shapes in the trace — candidate ids and mask
``[Bp, C]``, user rows ``[Bp, W]``, user offsets ``[Bp, 1]``, the
HBM-resident item plane ``[N, W]`` and the top-N scores and slots out —
the required HBM bytes are the F+1 useful lanes of each of the Bp·C
candidate rows (F from the configuration) and those of the other
operands and outputs the compiler left in HBM (not in on-chip memory,
layout ``S(n)``); the FLOPs are the 2·Bp·C·(F+1) of scoring.  The least
time is the larger of FLOPs over peak and bytes over HBM bandwidth.
"""
import math

import xplane


def call_cost(text: str, F: int) -> tuple[float, float]:
    _, ops = xplane.operand_shapes(text)
    Bp, C = ops[0][1]
    plane = ops[4][1]
    res, hbm = xplane.operand_shapes(text, hbm_only=True)
    # the plane is read only at the candidates' rows, counted apart
    words = (sum(math.prod(d) for _, d in hbm) - math.prod(plane)
             + Bp * C * (F + 1) + sum(math.prod(d) for _, d in res))
    return 2.0 * Bp * C * (F + 1), 4.0 * words


def share(calls, peak, F: int) -> float | None:
    if not calls:
        return None
    least = sum(max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b in (call_cost(t, F) for t, _ in calls))
    return 100.0 * least / sum(s for _, s in calls)


def read(run):
    if run.trace is None:
        return None
    return share(run.trace.kernel_calls("candidate_score_topn"), run.peak,
                 run.facts["F"])
