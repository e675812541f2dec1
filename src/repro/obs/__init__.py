"""Flight telemetry: the two-plane observability subsystem.

* **Device plane** (`obs/telemetry.py`): an opt-in ``ExecSpec.telemetry``
  knob makes the sync, sharded, and async engines emit a typed
  :class:`Telemetry` pytree as *extra scan outputs* riding the existing
  single device->host transfer — per-round cohort composition, buffer
  occupancy, staleness spread, traffic/energy splits, route hop counts.
  Telemetry **off** compiles the same instructions as an engine without
  any obs code (the ops' names carry the always-on ``fed_step/*`` and
  ``async_event/*`` phase scopes); telemetry **on** adds outputs only and
  never perturbs the model trajectory (both pinned by
  ``tests/test_obs.py`` / ``tests/test_sharded_engine.py``).
* **Host plane** (`obs/trace.py`): a span tracer wrapping setup / lower /
  compile / run / fetch / history, emitted as Chrome trace-event JSON
  loadable in Perfetto, plus process-wide counters: hit/miss on the
  AOT-executable and setup caches in `repro.api`, and backend compiles.

``RunResult.telemetry`` carries both planes (JSON round-trip through
``save``/``load``), and ``python -m repro.obs.report run.json`` renders a
round-by-round table, a phase-time breakdown, and the trace export.
"""
from repro.obs.telemetry import RunTelemetry, Telemetry, rounds_from_scan
from repro.obs.trace import COUNTERS, Tracer, phase_scope

__all__ = ["Telemetry", "RunTelemetry", "rounds_from_scan",
           "Tracer", "COUNTERS", "phase_scope"]
