"""Device milliseconds per round (sync) or event (async) of the
operations under ``fed_step/eval`` and ``async_event/eval``: the
test-set accuracy of the mean model at the eval rounds
(``lenet_accuracy`` under its ``lax.cond``)."""
import trace_reduce


def read(ctx):
    s = sum(trace_reduce.scope_seconds(ctx["trace"], f"{step}/eval")
            for step in ("fed_step", "async_event"))
    return s / ctx["units"] * 1e3 if s > 0 else None
