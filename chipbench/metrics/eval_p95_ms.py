"""eval_p95_ms: 95th percentile of every evaluation's wall time in the
window, from the call to the result being ready."""

import numpy as np


def read(rec):
    if rec.kind != "plan" or not rec.latencies_s:
        return None
    return float(np.percentile(rec.latencies_s, 95)) * 1e3
