"""Set-up seconds of the neighbour-plane pack: `fit`'s ``train.prep.pack``
span (`core.model.build_scheduled_data` and `build_shard_data`)."""


def read(run):
    s = run.span_s("train.prep.pack")
    return sum(s) if s else None
