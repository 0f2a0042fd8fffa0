"""Mean milliseconds a micro-batch waited to fill: the service's
``serve.flush.fill`` spans (submit of the batch's oldest request → start
of its dispatch) inside the traced window.

Registry spans reach the trace's clock through `repro.obs.trace_clock`,
fitted over the spans recorded on both; `on_trace` is that step for the
readers of the service's per-flush spans.  A program without the
function or without the spans gives nothing to read."""
import sys

from repro import obs


def on_trace(run, *names, say: bool = False):
    """{name: [(start, end)]} of the registry spans ``names`` on the
    trace's clock, or None when the program cannot put them there;
    ``say`` prints the fit to standard error."""
    fit = getattr(obs, "trace_clock", None)
    if run.trace is None or fit is None:
        return None
    clk = fit(run.spans, run.trace.host)
    if clk is None:
        return None
    if say:
        print(f"trace clock: {clk.twins} twins, rate - 1 = "
              f"{clk.rate - 1:.3e}, worst residual {clk.residual_us:.3f} us",
              file=sys.stderr)
    return {n: clk.place(run.spans, n) for n in names}


def inside(run, spans) -> list:
    return [(s, e) for s, e in spans
            if s >= run.trace.t0 and e <= run.trace.t1]


def read(run):
    on = on_trace(run, "serve.flush.fill", say=True)
    fill = inside(run, on["serve.flush.fill"]) if on else []
    return 1e-6 * sum(e - s for s, e in fill) / len(fill) if fill else None
