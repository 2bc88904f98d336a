"""Host time of the program's ``engine.pad`` spans (the cache lookup
and host dispatch of the device permute and zero-pad of the request
features, ``jit__stage_x``, whose device time `stage_device_ms_per_req`
reads) in the traced window, per request answered in it
(`bench.lib.marks`)."""
from bench.lib import marks


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.host_s("engine.pad")
    return None if s is None else s / len(run.answered) * 1e3
