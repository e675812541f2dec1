"""Device milliseconds per event of the operations under
``async_event/aggregate``: the buffered flush, the stage-2 aggregation
across clusters and the cohort's fetch of its cluster model."""
import trace_reduce


def read(ctx):
    s = trace_reduce.scope_seconds(ctx["trace"], "async_event/aggregate")
    return s / ctx["units"] * 1e3 if s > 0 else None
