"""The readers of the serving service's per-flush spans, on a synthesized
run: registry spans on one clock, the profiler's host annotations on
another, and its device programs 1.5 ms early against the host plane, as
a v5e's trace can have them; five flushes in the window."""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import harness  # noqa: E402
import xplane  # noqa: E402
from repro import obs  # noqa: E402

P = 1_000_000                    # one flush period, ns
BASE = 7_000_000_000_000         # registry time of the window's start
RATE, OFF = 1.0 + 2e-6, 5_000_000.0
EARLY = 1_500_000                # device plane against the host plane, ns
PLANE = "/device:TPU:0"
NEW = ("serve.fill_ms", "serve.ready_wait_ms", "serve.dispatch_ms",
       "device_idle.serve.fill", "device_idle.serve.host")


def tr(rel):
    """Trace time of registry time ``BASE + rel``."""
    return OFF + RATE * rel


def flushes():
    """Per flush k: fill, dispatch, take, launch, the flush span's start,
    its sync and its device program, in ns after BASE.  Flush 2's launch
    stalls 200 µs with the device idle; flush 3's program outlasts the
    next dispatch, so its sync blocks and returns as the program ends; the
    others' answers wait for the next dispatch."""
    device = {0: (670_000, 970_000), 1: (P + 670_000, P + 970_000),
              2: (2 * P + 880_000, 3 * P + 180_000),
              3: (3 * P + 670_000, 4 * P + 750_000),
              4: (4 * P + 760_000, 5 * P + 60_000)}
    syncs = {0: (P + 700_000, P + 710_000), 1: (2 * P + 900_000,
                                                2 * P + 910_000),
             2: (3 * P + 700_000, 3 * P + 710_000),
             3: (4 * P + 700_000, 4 * P + 750_000),
             4: (5 * P + 700_000, 5 * P + 710_000)}
    out = []
    for k in range(5):
        d0 = k * P + 600_000
        d1 = d0 + (300_000 if k == 2 else 100_000)
        out.append(dict(fill=(100_000 if k == 0 else k * P, d0),
                        dispatch=(d0, d1), take=(d0, d0 + 20_000),
                        launch=(d0 + 20_000, d1), flush0=d0 + 21_000,
                        sync=syncs[k], device=device[k]))
    return out


def make_run(new_spans=True):
    spans, host = [], []

    def span(name, a, b, twin=True):
        spans.append((name, BASE + a, b - a))
        if twin:
            host.append((name, tr(a), tr(b)))

    # a flush before the profiler started: no twin, no program in the trace
    span("serve.flush.dispatch", -9 * P, -9 * P + 100_000, twin=False)
    span("serve.flush", -9 * P + 21_000, -8 * P + 710_000, twin=False)
    for f in flushes():
        if new_spans:
            span("serve.flush.fill", *f["fill"], twin=False)
        span("serve.flush.dispatch", *f["dispatch"])
        if new_spans:
            span("serve.flush.dispatch.take", *f["take"])
            span("serve.flush.dispatch.launch", *f["launch"])
            span("serve.flush.sync", *f["sync"])
        span("serve.flush", f["flush0"], f["sync"][1], twin=False)
    window = (tr(0), tr(4 * P + 800_000))
    host.append(("bench.window", *window))
    trace = xplane.Trace(
        modules={PLANE: [("jit_recommend_walked_kernel(1)", tr(a) - EARLY,
                          tr(b) - EARLY)
                         for a, b in (f["device"] for f in flushes())]},
        host=host, t0=window[0], t1=window[1])
    return harness.Run(cfg={}, traffic={}, seed=0, seconds=1.0,
                       trace_on=True, t_start=0.0, trace=trace, spans=spans)


def reader(name):
    return harness.load_module(str(CHIP / "metrics" / f"{name}.py")).read


def test_clock_from_the_twins():
    run = make_run()
    clk = obs.trace_clock(run.spans, run.trace.host)
    assert clk.twins == 20                 # 4 context spans × 5 flushes
    assert clk.residual_us < 1.0 and abs(clk.rate - RATE) < 1e-9


def test_fill_and_dispatch_means():
    run = make_run()
    # fills of 500 µs and four of 600 µs; dispatches of 100 µs but one of
    # 300 µs (the stalled launch)
    assert reader("serve.fill_ms")(run) == pytest.approx(0.58, rel=1e-5)
    assert reader("serve.dispatch_ms")(run) == pytest.approx(0.14,
                                                             rel=1e-5)


def test_ready_wait():
    # flushes 0-3 lie in the window: their answers were ready 740, 940 and
    # 530 µs before the host's sync, flush 3's not at all (its sync
    # blocked); flush 4's sync falls after the window.  Taken as they lie
    # in the trace, each program would seem to start before its launch.
    run = make_run()
    wait = harness.load_module(str(CHIP / "metrics"
                                   / "serve.ready_wait_ms.py"))
    shift, rows = wait.on_host(run)
    assert shift == pytest.approx(EARLY, abs=1.0)
    assert len(rows) == 5                  # the flush before has no program
    assert wait.read(run) == pytest.approx((740 + 940 + 530 + 0) / 4 * 1e-3,
                                           rel=1e-5)


def test_idle_split_by_host_state():
    run = make_run()
    window = 4.8e6
    split = harness.load_module(str(CHIP / "metrics"
                                    / "device_idle.serve.fill.py"))
    idle = split.length(split.parts(run)[0]) / RATE
    fill = reader("device_idle.serve.fill")(run)
    host = reader("device_idle.serve.host")(run)
    # idle while dispatching or syncing: 70 µs before each of programs
    # 0, 1 and 3 (the launch) and the 280 µs stalled launch of flush 2
    assert host == pytest.approx(100 * 490_000 / window, rel=1e-5)
    # idle with a batch filling and the host elsewhere
    assert fill == pytest.approx(100 * (500 + 600 + 600 + 420) * 1e3
                                 / window, rel=1e-5)
    assert idle == pytest.approx(2_780_000, rel=1e-5)
    assert 0 < fill + host <= 100 * idle / window


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_without_the_program_parts(monkeypatch, name):
    """A program without the new spans, or without `trace_clock`, leaves
    every new reader with nothing to read; none of them raises."""
    read = reader(name)
    old = make_run(new_spans=False)
    if name in ("serve.dispatch_ms", "serve.ready_wait_ms"):
        assert read(old) is not None       # those spans were there before
    else:
        assert read(old) is None
    monkeypatch.delattr(obs, "trace_clock")
    assert read(make_run()) is None
    untraced = make_run()
    untraced.trace = None
    assert read(untraced) is None
