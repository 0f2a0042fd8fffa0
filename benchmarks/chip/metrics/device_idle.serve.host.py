"""Share of the traced serving window in which the device was idle while
the host was inside ``serve.flush.dispatch`` or ``serve.flush.sync``
(see device_idle.serve.fill for the intervals)."""
import os

import harness

idle = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "device_idle.serve.fill.py"))


def read(run):
    p = idle.parts(run)
    if p is None:
        return None
    gaps, _, host = p
    return 100.0 * idle.length(idle.intersect(gaps, host)) / (
        run.trace.t1 - run.trace.t0)
