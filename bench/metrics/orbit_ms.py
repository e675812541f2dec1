"""Device milliseconds per round (sync) or event (async) of the
operations under ``fed_step/orbit`` and ``async_event/orbit``: the
positions, the drift assignment, the contact-plan lookups, the gateway
choice and the cost model (``orbits/cost.py``; in the async engine the
route gating and the restart costs)."""
import trace_reduce


def read(ctx):
    s = sum(trace_reduce.scope_seconds(ctx["trace"], f"{step}/orbit")
            for step in ("fed_step", "async_event"))
    return s / ctx["units"] * 1e3 if s > 0 else None
