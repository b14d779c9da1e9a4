"""95th percentile over all requests of the window of the time from a
request's ``submit`` to its answer being ready (failed requests included)."""

import numpy as np


def value(run):
    if not run.latencies_s:
        return None
    return 1000.0 * float(np.percentile(run.latencies_s, 95))
