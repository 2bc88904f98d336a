"""Share of the traced window in which no XLA op ran on the device:
1 - (union of op intervals / window), averaged over the chips used."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
