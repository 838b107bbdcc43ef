"""call_p95_s: 95th percentile of the wall time of every call in the
window (linear interpolation between order statistics). Host clock."""

import numpy as np


def read(run):
    if not run.durations:
        return None
    return float(np.percentile(run.durations, 95))
