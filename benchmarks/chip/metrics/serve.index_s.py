"""Set-up seconds of the serving index: the benchmark's ``bench.index``
span around `simlsh.encode` and `serve.build_index`."""


def read(run):
    s = run.span_s("bench.index")
    return sum(s) if s else None
