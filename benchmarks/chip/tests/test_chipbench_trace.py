"""The trace reduction of the chip benchmark, on a synthesized trace."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import xplane  # noqa: E402

CULSH = ("%culsh_sgd_step.3 = (f32[33,512]{1,0:T(8,128)S(1)}, "
         "f32[97,512]{1,0:T(8,128)S(1)}) custom-call(f32[33,512]{1,0} %a, "
         "f32[97,512]{1,0} %b, f32[32,512]{1,0} %c, f32[32,512]{1,0} %d, "
         "f32[32,512]{1,0} %e, f32[1,512]{1,0} %f, f32[1,512]{1,0} %g, "
         "f32[13]{0} %h), custom_call_target=\"tpu_custom_call\"")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=())


def profile():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(1)", 100, 300),
                                       ev("jit_step(1)", 600, 200),
                                       ev("jit_step(1)", 950, 100)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = (s32[]) while(s32[] %x)", 100, 300),
            ev(CULSH, 120, 50), ev(CULSH.replace(".3 =", ".7 ="), 200, 70),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y)", 650, 100),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y)", 960, 20)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 50, 1000), ev("train.epoch.eval", 420, 150),
        ev("$api.py:1 block_until_ready", 400, 10)])])
    other = NS(name="/device:CUSTOM:x", lines=[])
    return NS(planes=[dev, host, other])


def test_window_busy_and_idle():
    tr = xplane.from_profile(profile(), window="bench.window")
    assert (tr.t0, tr.t1) == (50, 1050)
    assert abs(tr.window_s - 1000e-9) < 1e-15
    # programs run 100-400, 600-800, 950-1050
    assert abs(tr.busy_s - 600e-9) < 1e-15
    gaps = tr.idle_gaps(2)
    assert gaps[0][0] == "train.epoch.eval"
    assert abs(gaps[0][1] - 200e-9) < 1e-15
    assert gaps[1][0] == "bench.window" and abs(gaps[1][1] - 150e-9) < 1e-15


def test_kernel_calls_and_top_ops():
    tr = xplane.from_profile(profile(), window="bench.window")
    calls = tr.kernel_calls("culsh_sgd_step")
    assert len(calls) == 2
    assert abs(sum(s for _, s in calls) - 120e-9) < 1e-15
    top = tr.top_ops(10)
    names = [n for n, _ in top]
    assert not any(n.startswith("while") for n in names)  # holds its body
    assert names[0] == "fusion.2 f32[8]" and abs(top[0][1] - 120e-9) < 1e-15
    assert len(tr.module_calls("jit_step")) == 3


def test_window_defaults_to_device_span():
    tr = xplane.from_profile(profile())
    assert (tr.t0, tr.t1) == (100, 1050)


def test_on_chip_operands_left_out_of_hbm():
    text = ("%k.1 = (f32[4,8]{1,0:T(8,128)S(1)}, f32[2]{0}) custom-call("
            "f32[4,8]{1,0:T(8,128)S(1)} %a, s32[3]{0:T(128)} %b)")
    res, ops = xplane.operand_shapes(text, hbm_only=True)
    assert res == [("f32", (2,))] and ops == [("s32", (3,))]
    res, ops = xplane.operand_shapes(text)
    assert len(res) == 2 and len(ops) == 2


def test_operand_shapes():
    res, ops = xplane.operand_shapes(CULSH)
    assert res == [("f32", (33, 512)), ("f32", (97, 512))]
    assert [d for _, d in ops] == [(33, 512), (97, 512), (32, 512),
                                   (32, 512), (32, 512), (1, 512), (1, 512),
                                   (13,)]
    assert xplane.hlo_name(CULSH) == "culsh_sgd_step.3"
    assert xplane.base_name("culsh_sgd_step.3") == "culsh_sgd_step"
