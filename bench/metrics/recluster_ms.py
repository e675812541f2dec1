"""Device milliseconds per round of the operations under
``fed_step/recluster``: the dropout check and, in the rounds where it
fires, k-means, the MAML meta-update and the members' adaptation
(``core/fedhc.py`` ``_meta_update_clusters``, ``_adapt_members``)."""
import trace_reduce


def read(ctx):
    s = trace_reduce.scope_seconds(ctx["trace"], "fed_step/recluster")
    return s / ctx["units"] * 1e3 if s > 0 else None
