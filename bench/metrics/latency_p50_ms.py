"""Median latency over every answered request due in the window: open
loop from its due time, closed loop from the call, to the logits on the
host."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 50)) * 1e3
