"""Calls of ``repro.api.run`` that found their executable in the AOT
cache and still compiled something (``COUNTERS["api.compile_on_hit"]``).
The window's calls are the only AOT hits of a run, so this counts the
window calls that compiled; 0.0 where the program does not count it."""


def read(ctx):
    from repro.obs.trace import COUNTERS
    return float(COUNTERS.get("api.compile_on_hit"))
