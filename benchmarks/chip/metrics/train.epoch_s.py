"""Mean seconds of one training epoch in the window: `fit`'s
``train.epoch`` spans after the warm-up epochs (each ends in
`block_until_ready`; the held-out evaluation is outside them)."""


def read(run):
    s = run.span_s("train.epoch")[run.traffic["warmup_epochs"]:]
    return sum(s) / len(s) if s else None
