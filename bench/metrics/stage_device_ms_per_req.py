"""Device time of the program's staging executables in the traced
window, per request answered in it: XLA modules ``jit__stage_x`` (the
request features permuted and zero-padded into the graph's shape
class) and ``jit__unstage_y`` (the logits sliced and un-permuted back),
outside the forward's ``jit_fwd``."""

MODULES = ("jit__stage_x", "jit__unstage_y")


def read(run):
    if run.trace is None or not run.answered:
        return None
    s = sum(run.trace.module_s(m) for m in MODULES)
    if s <= 0:
        return None
    return s / len(run.answered) * 1e3
