"""Device milliseconds per round (sync) or event (async) of the
operations outside every phase: those whose named-scope path has no
``fed_step/`` or ``async_event/`` component, including those with no
path at all (copies the compiler adds)."""
import re

PHASE = re.compile(r"(^|[/(])(fed_step|async_event)/")


def read(ctx):
    s = sum(v for k, v in ctx["trace"]["scope_s"].items()
            if not PHASE.search(k))
    return s / ctx["units"] * 1e3 if s > 0 else None
