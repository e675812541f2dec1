"""Device milliseconds per event of the async engine's cohort work:
the operations under ``async_event/select`` (the earliest-deadline
``top_k`` pop) and ``async_event/contribute`` (staleness weights and
the scatter of the cohort's updates into the buffers)."""
import trace_reduce


def read(ctx):
    s = sum(trace_reduce.scope_seconds(ctx["trace"], f"async_event/{p}")
            for p in ("select", "contribute"))
    return s / ctx["units"] * 1e3 if s > 0 else None
