"""Mean milliseconds of a flush: the service's ``serve.flush`` spans
(dispatch to result ready, which includes waiting behind the flush
before it)."""


def read(run):
    s = run.span_s("serve.flush")
    return 1e3 * sum(s) / len(s) if s else None
