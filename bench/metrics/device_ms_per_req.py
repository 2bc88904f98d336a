"""Device time of the model's forward executables (XLA modules
``jit_fwd``, the program's `ExecutorCache` forward, single and
batched) in the traced window, per request answered in it."""

MODULE = "jit_fwd"


def read(run):
    if run.trace is None or not run.answered:
        return None
    s = run.trace.module_s(MODULE)
    if s <= 0:
        return None
    return s / len(run.answered) * 1e3
