"""Mean milliseconds an answer sat ready on the device before the host
collected it: for each ``serve.flush`` span (dispatch → the host's sync)
inside the traced window, the sync's return minus the end of the
flush's program.

The spans reach the trace's clock through `repro.obs.trace_clock` (see
serve.fill_ms), which fits them to the host plane.  The device planes
agree with the host plane only to a millisecond or two on a v5e
(programs have appeared up to 0.8 ms before the launch that started
them), so `on_host` first moves the device's times onto the host
plane: the flush program's executions (the module name the trace holds
most of, one per flush) are paired with the flushes in order, and
shifted by the least (sync return − program end), since a sync cannot
return before its program ends and one that blocked returns just after.
Of the pairings in which no program then starts before its flush's
launch, the one that needs the smallest shift is taken.  Without a sync
that blocked, the shift is too large and the waits are lower bounds."""
import collections
import os

import harness
import numpy as np

spans = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve.fill_ms.py"))


def on_host(run):
    """(shift, [(dispatch, sync return, program start, program end)]) on
    the host plane's clock, in ns, one row per flush with its program in
    the trace; None with nothing to read."""
    on = spans.on_trace(run, "serve.flush")
    if not on or not on["serve.flush"] or not run.trace.modules:
        return None
    mods = run.trace.modules[sorted(run.trace.modules)[0]]
    name = collections.Counter(n for n, _, _ in mods).most_common(1)[0][0]
    prog = np.asarray(sorted((s, e) for n, s, e in mods if n == name))
    fl = np.asarray(sorted(on["serve.flush"]))
    best = None
    for j in range(len(fl) - len(prog) + 1):      # program i ↔ flush i + j
        f = fl[j:j + len(prog)]
        lo = np.max(f[:, 0] - prog[:, 0])
        hi = np.min(f[:, 1] - prog[:, 1])
        if lo <= hi and (best is None or abs(hi) < abs(best[0])):
            best = (hi, j)
    if best is None:
        return None
    shift, j = best
    f = fl[j:j + len(prog)]
    return shift, np.column_stack([f, prog + shift])


def read(run):
    got = on_host(run)
    if got is None:
        return None
    rows = got[1]
    rows = rows[(rows[:, 0] >= run.trace.t0) & (rows[:, 1] <= run.trace.t1)]
    return float(np.mean(rows[:, 1] - rows[:, 3])) * 1e-6 if len(rows) \
        else None
