"""Device milliseconds per round of the operations under the
``fed_step/local_train`` scope (``core/fedhc.py`` ``_local_train``)."""
import trace_reduce


def read(ctx):
    s = trace_reduce.scope_seconds(ctx["trace"], "fed_step/local_train")
    return s / ctx["units"] * 1e3 if s > 0 else None
