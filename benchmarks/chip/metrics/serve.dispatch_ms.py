"""Mean milliseconds of host time to launch a flush: the service's
``serve.flush.dispatch`` spans (pop, join and pad the queue, then the
host → device copy and the program's launch) inside the traced
window, placed there by `repro.obs.trace_clock` (see serve.fill_ms)."""
import os

import harness

spans = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve.fill_ms.py"))


def read(run):
    on = spans.on_trace(run, "serve.flush.dispatch")
    d = spans.inside(run, on["serve.flush.dispatch"]) if on else []
    return 1e-6 * sum(e - s for s, e in d) / len(d) if d else None
