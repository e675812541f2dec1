"""Device milliseconds per round (sync) or event (async) of the
operations under ``fed_step/batches`` and ``async_event/batches``: the
minibatch picks and gathers that feed local training
(``client_batches``; in the async engine the cohort's samples and the
gather of its base parameters)."""
import trace_reduce


def read(ctx):
    s = sum(trace_reduce.scope_seconds(ctx["trace"], f"{step}/batches")
            for step in ("fed_step", "async_event"))
    return s / ctx["units"] * 1e3 if s > 0 else None
