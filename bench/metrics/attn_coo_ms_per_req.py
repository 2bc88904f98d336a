"""Device time of the ops in named scope ``attn.coo`` (the GAT's
attention weights and weighted sum on the COO triples) inside the
modules that carry the engine's scopes (the GAT executor's ``jit_fwd``)
in the traced window, per request answered in it (`bench.lib.marks`)."""
from bench.lib import marks

SCOPE = "attn.coo"


def read(run):
    m = marks.of_run(run)
    if m is None or not run.answered:
        return None
    s = m.scope_s(SCOPE)
    return None if s is None else s / len(run.answered) * 1e3
