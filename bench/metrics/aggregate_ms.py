"""Device milliseconds per round of the operations under the
``fed_step/aggregate`` scope (``core/aggregation_spmd.py``)."""
import trace_reduce


def read(ctx):
    s = trace_reduce.scope_seconds(ctx["trace"], "fed_step/aggregate")
    return s / ctx["units"] * 1e3 if s > 0 else None
