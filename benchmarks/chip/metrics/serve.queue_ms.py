"""Mean milliseconds from a request's due time to the dispatch of the
flush that carried it.  The k-th ``serve.flush`` span (dispatch → result
ready, recorded in dispatch order) carried requests k·B … k·B + B − 1 of
the window, B the micro-batch."""
import numpy as np


def read(run):
    fl = run.facts.get("flushes")
    if not fl:
        return None
    due = np.asarray(run.facts["due_ns"])
    start = np.repeat(np.asarray([t for t, _ in fl], float),
                      run.facts["micro_batch"])[:due.size]
    return float(np.mean(start - due[:start.size])) * 1e-6
