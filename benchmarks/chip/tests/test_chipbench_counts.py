"""The benchmark's FLOP and byte counts against hand counts at small
shapes, and the shares they give."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402

M = lambda name: harness.load_module(str(CHIP / "metrics" / f"{name}.py"))
PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def culsh_text(F, K, B):
    t = lambda r: f"f32[{r},{B}]{{1,0}}"
    return (f"%culsh_sgd_step.1 = ({t(F + 1)}, {t(F + 2 * K + 1)}) "
            f"custom-call({t(F + 1)} %a, {t(F + 2 * K + 1)} %b, {t(K)} %c, "
            f"{t(K)} %d, {t(K)} %e, {t(1)} %f, {t(1)} %g, f32[13]{{0}} %h)")


def test_culsh_counts_by_hand():
    mod = M("culsh_sgd_step_roofline")
    F, K, B = 2, 3, 4
    flops, nbytes = mod.call_cost(culsh_text(F, K, B))
    assert flops == (14 * F + 22 * K + 25) * B == 476
    # in: 3 + 9 + 3·3 + 1 + 1 rows of B, 13 scalars; out: 3 + 9 rows
    assert nbytes == 4 * ((3 + 9 + 9 + 2) * B + 13 + (3 + 9) * B) == 612
    # least time 612/10 s against 61.2 s measured → 100 %
    share = mod.share([(culsh_text(F, K, B), 61.2)], PEAK)
    assert abs(share - 100.0) < 1e-9


def test_culsh_operands_staged_on_chip_move_no_hbm_bytes():
    mod = M("culsh_sgd_step_roofline")
    text = culsh_text(2, 3, 4).replace("{1,0}", "{1,0:T(8,128)S(1)}")
    flops, nbytes = mod.call_cost(text)
    assert flops == 476 and nbytes == 4 * 13      # only the scalars


def test_candidate_score_counts_by_hand():
    mod = M("candidate_score_topn_roofline")
    text = ("%candidate_score_topn.1 = (f32[8,2]{1,0}, s32[8,2]{1,0}) "
            "custom-call(s32[8,4]{1,0} %c, f32[8,128]{1,0} %u, "
            "f32[8,1]{1,0} %b, f32[8,4]{1,0} %m, f32[100,128]{1,0} %p)")
    flops, nbytes = mod.call_cost(text, F=3)
    assert flops == 2 * 8 * 4 * 4
    # ids + mask 2·32, user rows 1024, offsets 8, rows 32·4, out 2·16
    assert nbytes == 4 * (64 + 1024 + 8 + 128 + 32)
    assert mod.share([], PEAK, 3) is None


def test_lsh_retrieve_counts_by_hand():
    mod = M("serve.mfu")
    text = ("%lsh_retrieve_topc.2 = s32[8,16]{1,0} custom-call("
            "s32[3]{0} %e, s32[8,128]{1,0} %pool)")
    assert mod.lsh_bytes(text) == 4 * (3 + 1024 + 128)
    # staged in on-chip memory, its operands move no HBM bytes
    staged = text.replace("{1,0}", "{1,0:T(8,128)S(1)}")
    assert mod.lsh_bytes(staged) == 4 * 3


class _ServeTrace:
    def __init__(self, calls):
        self.calls = calls

    def module_calls(self, prefix):
        return [("jit_recommend_walked_kernel(1)", 924.0)]

    def kernel_calls(self, name):
        return self.calls.get(name, [])


def test_serve_mfu_sums_both_kernels():
    lsh = ("%lsh_retrieve_topc.2 = s32[8,16]{1,0} custom-call("
           "s32[3]{0} %e, s32[8,128]{1,0} %pool)")
    cs = ("%candidate_score_topn.1 = (f32[8,2]{1,0}, s32[8,2]{1,0}) "
          "custom-call(s32[8,4]{1,0} %c, f32[8,128]{1,0} %u, "
          "f32[8,1]{1,0} %b, f32[8,4]{1,0} %m, f32[100,128]{1,0} %p)")
    run = harness.Run(cfg={}, traffic={}, seed=0, seconds=1, trace_on=True,
                      t_start=0.0, peak=PEAK)
    run.facts["F"] = 3
    assert M("serve.mfu").read(run) is None
    run.trace = _ServeTrace({"lsh_retrieve_topc": [(lsh, 1.0)]})
    # 4620 B / 10 B/s = 462 s of 924 s
    assert abs(M("serve.mfu").read(run) - 50.0) < 1e-9
    run.trace = _ServeTrace({"lsh_retrieve_topc": [(lsh, 1.0)],
                             "candidate_score_topn": [(cs, 1.0)]})
    # + max(256 FLOP / 100, 4 · 1256 B / 10) = 502.4 s
    assert abs(M("serve.mfu").read(run) - 100 * (462 + 502.4) / 924) < 1e-9


def test_train_mfu_per_sample():
    f, b = M("train.mfu").per_sample(32, 32)
    assert f == 14 * 32 + 22 * 32 + 25
    assert b == 4 * (66 + 194 + 128 + 3)


class _Trace:
    window_s = 2.0
    busy_s = 1.5

    def module_calls(self, prefix):
        return [("jit_train_epoch_scheduled(1)", 0.5)] * 2


def test_mfu_and_idle_readers():
    run = harness.Run(cfg={"fit": {"F": 32, "K": 32}}, traffic={}, seed=0,
                      seconds=1, trace_on=True, t_start=0.0, peak=PEAK)
    run.facts["n_train"] = 10
    run.trace = _Trace()
    f, b = M("train.mfu").per_sample(32, 32)
    want = 100 * max(20 * f / 100.0, 20 * b / 10.0) / 1.0
    assert abs(M("train.mfu").read(run) - want) < 1e-9
    assert abs(M("device_idle.train").read(run) - 25.0) < 1e-9
    run.trace = None
    assert M("train.mfu").read(run) is None
    assert M("culsh_sgd_step_roofline").read(run) is None


def test_queue_reader_matches_flushes_in_order():
    run = harness.Run(cfg={}, traffic={}, seed=0, seconds=1, trace_on=True,
                      t_start=0.0)
    run.facts.update(due_ns=[0, 10, 20, 30, 40], micro_batch=2,
                     flushes=[(15, 5), (35, 5), (60, 5)])
    # waits 15, 5, 15, 5, 20 ns
    assert abs(M("serve.queue_ms").read(run) - 12e-6) < 1e-12


def test_p99_reader_counts_every_request():
    run = harness.Run(cfg={}, traffic={}, seed=0, seconds=1, trace_on=True,
                      t_start=0.0)
    assert M("serve.p99_ms").read(run) is None
    lat = np.arange(1.0, 201.0)                   # 1 … 200 ms
    run.facts["latency_ms"] = lat
    assert M("serve.p99_ms").read(run) == 199.0   # nearest rank above
    lat[-3:] = np.inf                             # three never answered
    assert M("serve.p99_ms").read(run) == np.inf
