"""95th percentile of every request's latency, from its scheduled time."""
import numpy as np


def read(run, trace):
    if run.latencies_ms is None or len(run.latencies_ms) == 0:
        return None
    return float(np.percentile(run.latencies_ms, 95))
