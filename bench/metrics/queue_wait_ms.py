"""Median time a request of the window spent in the frontend's
``queue`` span (``RequestQueue.submit`` to its batch's close), from
the `repro.obs` tracer of a traced run."""
import numpy as np


def read(run):
    if not run.queue_waits_s:
        return None
    return float(np.median(run.queue_waits_s)) * 1e3
