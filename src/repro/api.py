"""One-call experiment API: ``run(scenario) -> RunResult``.

This is the public face of the repro: build a typed
:class:`~repro.core.scenario.Scenario` (orthogonal frozen sub-configs,
validated at construction), hand it to :func:`run`, get a typed
:class:`RunResult` back.  Routing is automatic:

* synchronous strategies execute on the scan engine
  (`repro.core.engine`), asynchronous (``async-buffered``) strategies on
  the event engine (`repro.core.async_engine`);
* ``scenario.exec.mesh_devices`` (or an explicit ``mesh=``) selects the
  client-axis SPMD program variant;
* the result is **bit-identical** to the corresponding legacy entrypoint
  (``engine.run`` / ``async_engine.run`` on ``scenario.to_flat()``) —
  pinned by ``tests/test_api.py`` — because both paths share the same
  ``setup``/``_scan_fn``/``history_from_outputs`` calls.

:class:`RunResult` replaces the untyped ``Dict[str, list]`` histories:
numpy arrays per eval point, resolved-strategy metadata, mesh shape,
setup/compile/run wall times, ``time_to_accuracy(target)`` (absorbing
``fedhc.time_energy_to_accuracy``), and JSON ``save``/``load`` so
benchmark results carry their exact scenario manifest.

:func:`run_sweep` is the multi-seed variant (one compiled vmap over the
seed axis, sync strategies only), returning a :class:`SweepResult`.

``scenario.exec.telemetry`` opts into the observability planes
(`repro.obs`): per-round device series riding the run's single
device→host transfer plus host phase spans and counter deltas, surfaced
as ``RunResult.telemetry`` and rendered by ``python -m
repro.obs.report``.  Off (the default) compiles the same instructions as
a program without any obs code — the ops' names carry the engines'
always-on phase scopes — and gives bit-identical histories; on never
changes the trajectory (pinned in ``tests/test_obs.py``).  Telemetry or
not, every host phase of :func:`run` enters a
``jax.profiler.TraceAnnotation`` named ``api.run/<phase>`` (``setup``,
``lower``, ``compile``, ``run``, ``fetch``, ``history``), so a profiler
capture puts each stretch of device idle time down to one of them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import numpy as np

from repro.core import strategies as strat_lib
from repro.core.scenario import (AsyncSpec, CommsSpec, DataSpec, ExecSpec,
                                 FleetSpec, Scenario, TrainSpec)
from repro.obs.telemetry import RunTelemetry, rounds_from_scan
from repro.obs.trace import COUNTERS, Counters, Tracer

__all__ = [
    "Scenario", "DataSpec", "FleetSpec", "TrainSpec", "CommsSpec",
    "AsyncSpec", "ExecSpec", "RunResult", "SweepResult", "TimeToAccuracy",
    "run", "run_sweep",
]


class TimeToAccuracy(NamedTuple):
    """First eval point at/after which accuracy reached the target."""
    time_s: float
    energy_j: float
    round: int


@dataclass
class RunResult:
    """Typed result of one :func:`run` call.

    Per-eval-point arrays (``round``/``acc``/``loss``/``time_s``/
    ``energy_j`` — cumulative simulated seconds/joules), run totals,
    the resolved strategy axes, and host-side timing breakdown.  The
    async-only telemetry fields (``flushes``/``mean_staleness``) are
    ``None`` for synchronous strategies."""
    scenario: Scenario
    round: np.ndarray          # (E,) int — 1-based eval round/event index
    acc: np.ndarray            # (E,) f64 test accuracy
    loss: np.ndarray           # (E,) f64 training loss
    time_s: np.ndarray         # (E,) f64 cumulative simulated time
    energy_j: np.ndarray       # (E,) f64 cumulative simulated energy
    reclusters: int
    global_rounds: int         # stage-2 aggregations that actually fired
    strategy: Dict[str, str]   # resolved Strategy axes (registry entry)
    mesh_shape: Optional[Dict[str, int]]   # None on the single-device path
    setup_s: float             # host: one-time eager setup
    compile_s: float           # host: XLA lower+compile of the scan
    run_s: float               # host: compiled execution, fetch
    #                            and history extraction
    flushes: Optional[int] = None
    mean_staleness: Optional[float] = None
    peak_device_mem_mb: Optional[float] = None  # max peak allocation over
    #                            ALL local devices (jax memory_stats;
    #                            None on backends that don't report,
    #                            e.g. CPU)
    peak_host_mem_mb: Optional[float] = None    # host peak RSS
    #                            (getrusage ru_maxrss) — the fallback
    #                            that exists on every backend
    telemetry: Optional["RunTelemetry"] = None  # both obs planes when
    #                            ExecSpec.telemetry is on (repro.obs):
    #                            per-round device series + host spans +
    #                            cache counters; rides save/load
    executable: Any = dataclasses.field(default=None, repr=False,
                                        compare=False)
    #                            the AOT-compiled scan that ran
    #                            (``memory_analysis()``, ``as_text()``);
    #                            not saved

    # ------------------------------------------------------------------
    @property
    def wall_s(self) -> float:
        """Total host wall-clock: setup + compile + run."""
        return self.setup_s + self.compile_s + self.run_s

    @property
    def final_acc(self) -> float:
        return float(self.acc[-1])

    def time_to_accuracy(self, target: float) -> Optional[TimeToAccuracy]:
        """First ``(time_s, energy_j, round)`` at which accuracy reached
        ``target``.  Returns **None** when the target is never reached
        (callers wanting the legacy sentinel can treat None as
        time=energy=inf; `fedhc.time_energy_to_accuracy` keeps that
        convention for history dicts)."""
        for r, a, t, e in zip(self.round, self.acc, self.time_s,
                              self.energy_j):
            if a >= target:
                return TimeToAccuracy(float(t), float(e), int(r))
        return None

    def to_history(self) -> Dict[str, list]:
        """The legacy ``engine.run``-style history dict, bit-identical to
        what the flat entrypoint returns for ``scenario.to_flat()``."""
        h: Dict[str, Any] = {
            "round": [int(r) for r in self.round],
            "acc": [float(a) for a in self.acc],
            "loss": [float(x) for x in self.loss],
            "time_s": [float(t) for t in self.time_s],
            "energy_j": [float(e) for e in self.energy_j],
            "reclusters": self.reclusters,
            "global_rounds": self.global_rounds,
        }
        if self.flushes is not None:
            h["flushes"] = self.flushes
            h["mean_staleness"] = self.mean_staleness
        return h

    # ---- persistence ---------------------------------------------------
    def save(self, path: str) -> None:
        """JSON result-with-manifest: the exact scenario rides along, so
        a saved result is reproducible by construction."""
        d = {
            "scenario": self.scenario.to_dict(),
            "history": self.to_history(),
            "strategy": self.strategy,
            "mesh_shape": self.mesh_shape,
            "timings": {"setup_s": self.setup_s,
                        "compile_s": self.compile_s,
                        "run_s": self.run_s,
                        "peak_device_mem_mb": self.peak_device_mem_mb,
                        "peak_host_mem_mb": self.peak_host_mem_mb},
            "telemetry": (self.telemetry.to_dict()
                          if self.telemetry is not None else None),
        }
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(d, f, indent=2)

    @classmethod
    def load(cls, path: str) -> "RunResult":
        with open(path) as f:
            d = json.load(f)
        h, t = d["history"], d["timings"]
        return cls(
            scenario=Scenario.from_dict(d["scenario"]),
            round=np.asarray(h["round"], np.int64),
            acc=np.asarray(h["acc"], np.float64),
            loss=np.asarray(h["loss"], np.float64),
            time_s=np.asarray(h["time_s"], np.float64),
            energy_j=np.asarray(h["energy_j"], np.float64),
            reclusters=h["reclusters"],
            global_rounds=h["global_rounds"],
            strategy=d["strategy"],
            mesh_shape=d["mesh_shape"],
            setup_s=t["setup_s"], compile_s=t["compile_s"],
            run_s=t["run_s"],
            flushes=h.get("flushes"),
            mean_staleness=h.get("mean_staleness"),
            peak_device_mem_mb=t.get("peak_device_mem_mb"),
            peak_host_mem_mb=t.get("peak_host_mem_mb"),
            telemetry=(RunTelemetry.from_dict(d["telemetry"])
                       if d.get("telemetry") else None),
        )


@dataclass
class SweepResult:
    """Typed result of :func:`run_sweep`: per-seed per-round arrays of
    shape ``(num_seeds, rounds)``; mask columns by ``evaluated`` (same
    cadence every seed) to recover the eval-point history."""
    scenario: Scenario
    seeds: np.ndarray          # (S,)
    acc: np.ndarray            # (S, R) — NaN on non-eval rounds
    loss: np.ndarray           # (S, R)
    time_s: np.ndarray         # (S, R)
    energy_j: np.ndarray       # (S, R)
    evaluated: np.ndarray      # (S, R) bool
    reclusters: np.ndarray     # (S,) per-seed totals
    global_rounds: np.ndarray  # (S,)
    wall_s: float

    @property
    def eval_rounds(self) -> np.ndarray:
        """1-based round indices of the eval points (cadence is identical
        across seeds)."""
        return np.nonzero(self.evaluated[0])[0] + 1

    def eval_curves(self, key: str = "acc") -> np.ndarray:
        """(S, E) per-seed values at the eval points only."""
        return getattr(self, key)[:, np.nonzero(self.evaluated[0])[0]]

    @property
    def final_acc(self) -> np.ndarray:
        """(S,) last-eval-point accuracy per seed."""
        return self.eval_curves("acc")[:, -1]

    # ---- persistence (the PR 5 follow-up: RunResult had it, SweepResult
    # ---- did not) ------------------------------------------------------
    def save(self, path: str) -> None:
        """JSON sweep-with-manifest: the exact scenario rides along (seeds
        come from the ``seeds`` array; ``scenario.seed`` is inert).  NaN
        entries (non-eval rounds) are encoded as JSON ``null`` so the file
        stays standard-compliant; :meth:`load` restores them exactly."""
        def col(a):
            a = np.asarray(a, np.float64)
            return [[None if np.isnan(x) else float(x) for x in row]
                    for row in a]
        d = {
            "scenario": self.scenario.to_dict(),
            "seeds": [int(s) for s in self.seeds],
            "acc": col(self.acc), "loss": col(self.loss),
            "time_s": col(self.time_s), "energy_j": col(self.energy_j),
            "evaluated": np.asarray(self.evaluated, bool).tolist(),
            "reclusters": [int(x) for x in self.reclusters],
            "global_rounds": [int(x) for x in self.global_rounds],
            "wall_s": self.wall_s,
        }
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(d, f, indent=2)

    @classmethod
    def load(cls, path: str) -> "SweepResult":
        with open(path) as f:
            d = json.load(f)

        def col(rows):
            return np.asarray([[np.nan if x is None else x for x in row]
                               for row in rows], np.float64)
        return cls(
            scenario=Scenario.from_dict(d["scenario"]),
            seeds=np.asarray(d["seeds"], np.int64),
            acc=col(d["acc"]), loss=col(d["loss"]),
            time_s=col(d["time_s"]), energy_j=col(d["energy_j"]),
            evaluated=np.asarray(d["evaluated"], bool),
            reclusters=np.asarray(d["reclusters"], np.int64),
            global_rounds=np.asarray(d["global_rounds"], np.int64),
            wall_s=d["wall_s"])


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------


# AOT-compiled scan executables, keyed like the engines' _scan_fn caches.
# AOT (lower+compile) gives RunResult a real compile_s split, but bypasses
# jit's own executable cache — this dict restores call-to-call reuse, so
# repeated api.run calls on one scenario (e.g. looping run() over seeds)
# pay XLA compilation once.  Input avals/shardings are fully determined by
# the key: setup() is deterministic in shapes for a given (cfg, mesh,
# client_axes), so a cached executable always matches.
_COMPILED: Dict[Any, Any] = {}


def _peak_device_mem_mb(devices) -> Optional[float]:
    """Max peak memory in MB across the devices the run used: per device
    the larger of the peak of live buffers and the peak reserved (a TPU
    counts the compiled program's scratch in the reserved bytes only),
    or None when the backend does not report memory stats (CPU returns
    None).  Device-0-only would under-report any run whose client shards
    are imbalanced or whose collectives stage on another device."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        got = [st[k] for k in ("peak_bytes_in_use", "peak_bytes_reserved")
               if st.get(k) is not None]
        if got:
            peaks.append(float(max(got)))
    return round(max(peaks) / 1e6, 3) if peaks else None


def _peak_host_mem_mb() -> Optional[float]:
    """Host peak RSS in MB (``getrusage`` ru_maxrss) — the memory
    telemetry that exists on every backend, including CPU where device
    memory_stats returns nothing.  ru_maxrss is KB on Linux, bytes on
    macOS; None where the resource module is unavailable (Windows)."""
    try:
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    except Exception:
        return None
    scale = 1.0 if sys.platform == "darwin" else 1024.0
    return round(float(peak) * scale / 1e6, 3)


def _setup_cache_key(cfg, mesh, caxes):
    """Setup is independent of the execution-only knobs (microbatch,
    Pallas routing, telemetry) — normalize those away so benchmark grid
    cells that vary only execution share one cached setup."""
    return (dataclasses.replace(cfg, client_microbatch=0,
                                use_pallas_kernels=False,
                                telemetry=False), mesh, caxes)


def _resolve_mesh(scenario: Scenario, mesh):
    """An explicit ``mesh=`` wins; otherwise build one from the ExecSpec
    (``None`` => single-program, ``0`` => every local device)."""
    if mesh is not None:
        return mesh
    md = scenario.exec.mesh_devices
    if md is None:
        return None
    from repro.launch import mesh as mesh_lib
    return mesh_lib.make_client_mesh(md or None)


def run(scenario: Scenario, *, verbose: bool = False, mesh=None,
        client_axes=None,
        setup_cache: Optional[Dict[Any, Any]] = None) -> RunResult:
    """Run one scenario end-to-end and return a :class:`RunResult`.

    Sync/async/sharded routing is automatic from the scenario's resolved
    strategy and :class:`ExecSpec`; ``mesh=``/``client_axes=`` override
    the ExecSpec placement for callers that already hold a mesh.  The
    trajectory is bit-identical to ``engine.run(scenario.to_flat())``
    (and the async route to ``async_engine.run``) — same setup, same
    compiled scan, same history extraction.

    ``setup_cache``: pass any dict (owned by the caller) to reuse the
    eager setup — dataset, model init, clustering, contact plan, device
    placement — across calls that differ only in execution knobs
    (microbatch, Pallas routing).  A hit reports ``setup_s ~ 0``.  Safe
    because the compiled scan never donates or mutates its inputs.
    Benchmarks sweeping variants at fixed N (`benchmarks/scale_bench.py`)
    use this to pay the ~10 s setup once per grid column."""
    from repro.core import engine
    cfg = scenario.to_flat()
    strategy = strat_lib.get(cfg.method)
    if strategy.is_async:
        from repro.core import async_engine as eng
    else:
        eng = engine
    mesh = _resolve_mesh(scenario, mesh)
    caxes = engine._resolve_client_axes(
        mesh, client_axes if client_axes is not None
        else scenario.exec.client_axes)
    if mesh is not None and strategy.shardable:
        from repro.launch import mesh as mesh_lib
        mesh_lib.validate_client_sharding(mesh, caxes, cfg.num_clients)

    # host-plane observability: every phase enters a profiler annotation
    # on the device trace's clock; a span tracer records them when
    # telemetry is on (the spans ride RunResult.telemetry); counters
    # always — counting is free and the cache tests assert on
    # repro.obs.trace.COUNTERS
    telem_on = scenario.exec.telemetry
    tracer = Tracer() if telem_on else None
    counters0 = COUNTERS.snapshot() if telem_on else {}
    compiles0 = COUNTERS.get("jax.backend_compile")

    @contextlib.contextmanager
    def span(name):
        with jax.profiler.TraceAnnotation("api.run/" + name), \
                (tracer.span(name) if tracer is not None
                 else contextlib.nullcontext()):
            yield

    t0 = time.perf_counter()
    skey = (_setup_cache_key(cfg, mesh, caxes)
            if setup_cache is not None else None)
    if skey is not None and skey in setup_cache:
        COUNTERS.inc("api.setup_cache.hit")
        state0, data = setup_cache[skey]
    else:
        if skey is not None:
            COUNTERS.inc("api.setup_cache.miss")
        with span("setup"):
            state0, data = eng.setup(cfg, mesh=mesh, client_axes=caxes)
            jax.block_until_ready((state0, data))
        if skey is not None:
            setup_cache[skey] = (state0, data)
    setup_s = time.perf_counter() - t0

    # the scan program is seed-independent (the seed is consumed by
    # setup), so seed-normalize both the cache key and the traced config:
    # looping run() over seeds — the path run_sweep's errors recommend —
    # compiles once and occupies ONE _scan_fn lru slot.  The inputs'
    # placement (which device or mesh) and the matmul precision in force
    # select different executables for one config.
    cfg0 = dataclasses.replace(cfg, seed=0)
    placement = jax.tree_util.tree_leaves(state0)[0].sharding
    key = (cfg0, mesh, caxes, placement,
           jax.config.jax_default_matmul_precision)
    compiled = _COMPILED.get(key)
    aot_hit = compiled is not None
    t0 = time.perf_counter()
    if not aot_hit:
        COUNTERS.inc("api.aot_cache.miss")
        fn = eng._scan_fn(cfg0, mesh, caxes)
        with span("lower"):
            lowered = fn.lower(state0, data)
        with span("compile"):
            compiled = lowered.compile()
        if len(_COMPILED) >= 32:                # same bound as _scan_fn's
            _COMPILED.pop(next(iter(_COMPILED)))
        _COMPILED[key] = compiled
    else:
        COUNTERS.inc("api.aot_cache.hit")
    compile_s = time.perf_counter() - t0        # ~0 on a cache hit

    t0 = time.perf_counter()
    with span("run"):
        _, outs = compiled(state0, data)
        jax.block_until_ready(outs)
    with span("fetch"):
        outs = jax.device_get(outs)                 # the one transfer
    round_outs, scan_telem = engine.split_outputs(outs)
    with span("history"):
        history = eng.history_from_outputs(round_outs)
    run_s = time.perf_counter() - t0
    if aot_hit and COUNTERS.get("jax.backend_compile") != compiles0:
        # the executable was cached, yet something on the call compiled
        COUNTERS.inc("api.compile_on_hit")

    if verbose:
        for r, a, l, t, e in zip(history["round"], history["acc"],
                                 history["loss"], history["time_s"],
                                 history["energy_j"]):
            print(f"[{cfg.method}] round {r:5d} acc={a:.3f} loss={l:.3f} "
                  f"T={t:.0f}s E={e:.1f}J")

    run_telem = None
    if telem_on:
        run_telem = RunTelemetry(
            rounds=(rounds_from_scan(scan_telem)
                    if scan_telem is not None else {}),
            spans=tracer.span_dicts(),
            counters=Counters.delta(counters0, COUNTERS.snapshot()))

    return RunResult(
        scenario=scenario,
        round=np.asarray(history["round"], np.int64),
        acc=np.asarray(history["acc"], np.float64),
        loss=np.asarray(history["loss"], np.float64),
        time_s=np.asarray(history["time_s"], np.float64),
        energy_j=np.asarray(history["energy_j"], np.float64),
        reclusters=history["reclusters"],
        global_rounds=history["global_rounds"],
        strategy=dataclasses.asdict(strategy),
        mesh_shape=dict(mesh.shape) if mesh is not None else None,
        setup_s=round(setup_s, 4), compile_s=round(compile_s, 4),
        run_s=round(run_s, 4),
        flushes=history.get("flushes"),
        mean_staleness=history.get("mean_staleness"),
        peak_device_mem_mb=_peak_device_mem_mb(placement.device_set),
        peak_host_mem_mb=_peak_host_mem_mb(),
        telemetry=run_telem,
        executable=compiled,
    )


def run_sweep(scenario: Scenario,
              seeds: Sequence[int]) -> SweepResult:
    """Multi-seed sweep: ONE compiled vmap over the seed axis
    (`engine.run_many_seeds`), ``scenario.seed`` ignored in favor of
    ``seeds``.  Sync single-program strategies only; sliced contact
    plans are per-seed and therefore rejected — every unsupported
    combination raises a clear ``ValueError`` before any compilation."""
    strategy = strat_lib.get(scenario.method)
    if strategy.is_async:
        raise ValueError(
            f"run_sweep is sync-only: {scenario.method!r} uses "
            f"async-buffered aggregation (vmapping the event scan over "
            f"seeds is an open ROADMAP item). Loop run() over seeds "
            f"instead.")
    # (contact_slices scenarios are rejected by run_many_seeds itself,
    # before any setup or compilation — one guard, one message)
    if scenario.exec.mesh_devices is not None:
        raise ValueError(
            "run_sweep does not support a client mesh yet "
            "(run_many_seeds vmaps the single-program scan; sharding the "
            "seed x client axes is an open ROADMAP item). Set "
            "ExecSpec(mesh_devices=None), or loop run() over seeds for "
            "sharded execution.")
    from repro.core import engine
    cfg = scenario.to_flat()
    t0 = time.perf_counter()
    sweep = engine.run_many_seeds(cfg, seeds)
    wall_s = time.perf_counter() - t0
    return SweepResult(
        scenario=scenario, seeds=sweep["seeds"], acc=sweep["acc"],
        loss=sweep["loss"], time_s=sweep["time_s"],
        energy_j=sweep["energy_j"], evaluated=sweep["evaluated"],
        reclusters=sweep["reclusters"],
        global_rounds=sweep["global_rounds"], wall_s=round(wall_s, 4))
