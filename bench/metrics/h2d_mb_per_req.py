"""Megabytes (1e6 bytes) of unpadded ``[n_cols, f]`` request features
the program put on the device in the traced run (the ``bytes`` of its
``engine.h2d`` spans, what its counter ``engine.h2d_bytes`` adds), per
request the window dispatched (`bench.lib.marks`)."""
from bench.lib import marks


def read(run):
    m = marks.of_run(run)
    sent = [r for r in run.records if not r.refused]
    if m is None or m.h2d_bytes is None or not sent:
        return None
    return m.h2d_bytes / len(sent) / 1e6
