"""Host-plane span tracer + process-wide cache and compile counters.

:class:`Tracer` wraps host phases (setup / lower / compile / run / fetch
/ history, benchmark cells, ...) in nested spans recorded against one
wall-clock epoch.  (`repro.api.run` enters a
``jax.profiler.TraceAnnotation`` per phase itself, telemetry on or off,
so an XLA profiler capture shows the same phase boundaries.)
``Tracer.span_dicts()`` is the JSON-ready record that rides
``RunResult.telemetry``; the Chrome trace-event rendering lives in
`obs.telemetry.RunTelemetry.to_chrome_trace`.

:data:`COUNTERS` is the process-wide counter registry.  `repro.api.run`
increments ``api.setup_cache.hit/miss`` (the caller-owned ``setup_cache``
dict) and ``api.aot_cache.hit/miss`` (the seed-normalized AOT executable
cache) on every call — always, telemetry on or off: counting is host-side
and free, and the cache tests assert on it directly.  Importing this
module registers a ``jax.monitoring`` listener that counts every backend
compile (or load from the persistent compilation cache) of the process
as ``jax.backend_compile``; `repro.api.run` counts a call that hit the
AOT cache while that counter still moved as ``api.compile_on_hit``.

:func:`phase_scope` is the in-scan marker: a ``jax.named_scope`` the
engines put around every phase of a round or event (``fed_step/*``,
``async_event/*``), so HLO op metadata (and thus the ``tf_op`` of each
operation in an XLA profiler trace) carries the phase names.  A named
scope changes op names only: the program compiles to the same
instructions with or without it.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List

import jax
import jax.monitoring


@dataclass
class Span:
    """One closed host span, relative to its tracer's epoch."""
    name: str
    ts_us: float
    dur_us: float
    depth: int
    args: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "ts_us": round(self.ts_us, 3),
                "dur_us": round(self.dur_us, 3), "depth": self.depth,
                "args": self.args}


class Tracer:
    """Nested wall-clock spans."""

    def __init__(self) -> None:
        self._epoch = time.perf_counter()
        self._depth = 0
        self.spans: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, **args: Any) -> Iterator[None]:
        """``with tracer.span("compile"): ...`` — records one span;
        nesting depth follows the with-stack."""
        depth, self._depth = self._depth, self._depth + 1
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self._depth = depth
            self.spans.append(Span(name, (t0 - self._epoch) * 1e6,
                                   (t1 - t0) * 1e6, depth, dict(args)))

    def span_dicts(self) -> List[Dict[str, Any]]:
        """JSON-ready span records, in start order."""
        return [s.to_dict() for s in sorted(self.spans,
                                            key=lambda s: s.ts_us)]

    def phase_times(self) -> Dict[str, float]:
        """Top-level span name -> total seconds."""
        out: Dict[str, float] = {}
        for s in self.spans:
            if s.depth == 0:
                out[s.name] = out.get(s.name, 0.0) + s.dur_us / 1e6
        return out


class Counters:
    """Thread-safe monotonic counters (process-wide singleton below)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._c: collections.Counter = collections.Counter()

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._c[name] += n

    def get(self, name: str) -> int:
        with self._lock:
            return self._c[name]

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._c)

    @staticmethod
    def delta(before: Dict[str, int],
              after: Dict[str, int]) -> Dict[str, int]:
        """Per-run counter increments between two snapshots."""
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v - before.get(k, 0)}


COUNTERS = Counters()

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_backend_compile(event: str, duration: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        COUNTERS.inc("jax.backend_compile")


jax.monitoring.register_event_duration_secs_listener(_count_backend_compile)


def phase_scope(name: str):
    """``jax.named_scope(name)``: names the HLO metadata of every op
    traced inside, so XLA profiler rows line up with engine phases."""
    return jax.named_scope(name)
