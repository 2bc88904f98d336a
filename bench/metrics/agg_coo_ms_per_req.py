"""Device time of the ops in named scope ``agg.coo`` (the COO engine's
share of ``A @ XW``) inside the modules that carry the engine's scopes
(the GCN executor's ``jit_fwd``) in the traced window, per request
answered in it (`bench.lib.marks`)."""
from bench.lib import marks

SCOPE = "agg.coo"


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.scope_s(SCOPE)
    return None if s is None else s / len(run.answered) * 1e3
