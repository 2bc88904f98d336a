"""Host time of the program's ``engine.pad`` spans (permute and zero-pad
of the request features on the host) in the traced window, per request
answered in it (`bench.lib.marks`)."""
from bench.lib import marks


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.host_s("engine.pad")
    return None if s is None else s / len(run.answered) * 1e3
