"""90th percentile of the latencies `latency_p50_ms` takes the median
of: the highest percentile with ten or more samples beyond it in the
slowest cell's window."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 90)) * 1e3
