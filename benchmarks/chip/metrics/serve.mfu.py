"""The whole flush's share of the chip's peak: the least time of the
work the flush requires — the required work of its two kernels — over
the device time of the traced flush programs
(``jit_recommend_walked_kernel``).  `candidate_score_topn` counts as its
roofline reader counts it; `lsh_retrieve_topc` does integer work only,
so it counts by its HBM bytes: the exclude list ``[E]`` and the window
pool ``[Bp, Wp]`` in, the candidate ids ``[Bp, C]`` out, all int32,
those the compiler left in HBM (not in on-chip memory, layout ``S(n)``).
"""
import math
import os

import harness
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def lsh_bytes(text: str) -> float:
    res, ops = xplane.operand_shapes(text, hbm_only=True)
    return 4.0 * (sum(math.prod(d) for _, d in ops)
                  + sum(math.prod(d) for _, d in res))


def read(run):
    if run.trace is None:
        return None
    mods = run.trace.module_calls("jit_recommend_walked_kernel")
    if not mods:
        return None
    cs = harness.load_module(os.path.join(HERE,
                                          "candidate_score_topn_roofline.py"))
    pk, F = run.peak, run.facts["F"]
    least = sum(lsh_bytes(t) for t, _ in
                run.trace.kernel_calls("lsh_retrieve_topc")) / pk[
        "hbm_bytes_per_s"]
    for t, _ in run.trace.kernel_calls("candidate_score_topn"):
        f, b = cs.call_cost(t, F)
        least += max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / sum(s for _, s in mods)
