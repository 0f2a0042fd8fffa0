"""Share of the traced serving window in which the device was idle while
a micro-batch filled (a ``serve.flush.fill`` span open) and the host was
in neither ``serve.flush.dispatch`` nor ``serve.flush.sync``.  Idle is
the complement of the union of program executions that
``device_idle.serve`` reads, moved onto the host plane's clock by the
shift that serve.ready_wait_ms works out; the spans reach that clock
through `repro.obs.trace_clock` (see serve.fill_ms)."""
import os

import harness
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
spans = harness.load_module(os.path.join(HERE, "serve.fill_ms.py"))
wait = harness.load_module(os.path.join(HERE, "serve.ready_wait_ms.py"))


def intersect(a, b) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(iv) -> float:
    return sum(e - s for s, e in iv)


def parts(run):
    """(idle, fill, host) on the host plane's clock, each a sorted list of
    disjoint intervals inside the window; None with nothing to read."""
    got = wait.on_host(run)
    on = spans.on_trace(run, "serve.flush.fill", "serve.flush.dispatch",
                        "serve.flush.sync")
    if got is None or not on["serve.flush.fill"]:
        return None
    t0, t1 = run.trace.t0, run.trace.t1
    clip = lambda iv: [tuple(x) for x in xplane._union(
        (max(s, t0), min(e, t1)) for s, e in iv if e > t0 and s < t1)]
    busy = clip((s + got[0], e + got[0]) for _, s, e in
                run.trace.modules[sorted(run.trace.modules)[0]])
    idle, prev = [], t0
    for s, e in busy + [(t1, t1)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    return (idle, clip(on["serve.flush.fill"]),
            clip(on["serve.flush.dispatch"] + on["serve.flush.sync"]))


def read(run):
    p = parts(run)
    if p is None:
        return None
    idle, fill, host = p
    idle_fill = intersect(idle, fill)
    return 100.0 * (length(idle_fill) - length(intersect(idle_fill, host))
                    ) / (run.trace.t1 - run.trace.t0)
