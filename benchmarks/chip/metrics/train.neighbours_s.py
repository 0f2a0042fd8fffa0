"""Set-up seconds of the neighbour search: `fit`'s ``train.neighbours``
span (simLSH's `core.simlsh.encode` and `core.topk` top-K, each in a
span of its own inside it)."""


def read(run):
    s = run.span_s("train.neighbours")
    return sum(s) if s else None
