"""Host time of the program's ``engine.h2d`` spans (``jnp.asarray`` /
``device_put`` of the unpadded, unpermuted ``[n_cols, f]`` request
features, on the calling thread) in the traced window, per request
answered in it (`bench.lib.marks`)."""
from bench.lib import marks


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.host_s("engine.h2d")
    return None if s is None else s / len(run.answered) * 1e3
