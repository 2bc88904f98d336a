"""Device time of the ops in named scope ``attn.score`` (the GAT's
per-vertex projections ``s_l``, ``s_r`` and the exact per-head row max,
a masked max on each of the three engines) inside the modules that
carry the engine's scopes (the GAT executor's ``jit_fwd``) in the traced
window, per request answered in it (`bench.lib.marks`)."""
from bench.lib import marks

SCOPE = "attn.score"


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.scope_s(SCOPE)
    return None if s is None else s / len(run.answered) * 1e3
