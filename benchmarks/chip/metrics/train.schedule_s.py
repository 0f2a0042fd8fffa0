"""Set-up seconds of the conflict-free schedule: `fit`'s
``train.prep.schedule`` span (`data.sparse.conflict_free_schedule`)."""


def read(run):
    s = run.span_s("train.prep.schedule")
    return sum(s) if s else None
