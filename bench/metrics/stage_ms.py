"""Mean host staging time of a dispatched group over the window (pad,
permute, stack, host-to-device copy): ``serving.staging_s``."""


def read(run):
    c = run.counters
    if not c or not c["staging_n"]:
        return None
    return c["staging_s"] / c["staging_n"] * 1e3
