"""Device milliseconds per event of the operations under
``async_event/local_train``: the cohort's local SGD (``core/fedhc.py``
``_local_train`` on the gathered sub-stack)."""
import trace_reduce


def read(ctx):
    s = trace_reduce.scope_seconds(ctx["trace"], "async_event/local_train")
    return s / ctx["units"] * 1e3 if s > 0 else None
