"""Compile seconds: ``RunResult.compile_s`` of the run's first
``api.run`` call (the AOT lower and compile of the scan, or its load
from the persistent compilation cache)."""


def read(ctx):
    return ctx["compile_s"]
