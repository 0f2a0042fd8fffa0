"""99th percentile of the request latency, in milliseconds, by nearest
rank over every request of the window: from its due time to the moment
its answer came back (never answered: infinitely late).  One stall of
the host in the window sets it, so it stands beside the end-to-end
``serve_p90_ms``."""
import numpy as np


def read(run):
    lat = run.facts.get("latency_ms")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 99, method="higher"))
