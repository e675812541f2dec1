"""Device-plane telemetry: the typed per-round pytree the engines emit.

:class:`Telemetry` is a NamedTuple of scalars (plus one ``(K,)`` vector)
computed *inside* the compiled round/event scan from intermediates the
engines already hold — cohort composition, buffer occupancy, staleness
spread, per-stage simulated traffic, the compute/comm energy split, and
ISL route hop counts.  It rides the scan's stacked outputs, so enabling
telemetry adds **zero** extra device->host syncs: the one end-of-run
transfer simply carries a few more small arrays.

The hard invariant (pinned by ``tests/test_obs.py`` and the sharded
subprocess tests): every telemetry value is a *new output* derived from
existing intermediates — nothing feeds back into the carry — so the model
trajectory with telemetry on is identical to telemetry off, and telemetry
off compiles the same instructions as an engine without any obs code;
only the ops' names differ, carrying the engines' phase scopes.

:class:`RunTelemetry` is the host-side container surfaced as
``RunResult.telemetry``: the fetched per-round series, the host span
records (`obs/trace.py`), and cache counters — JSON round-trippable and
exportable as Chrome trace-event JSON (`to_chrome_trace`) for Perfetto.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np


class Telemetry(NamedTuple):
    """Per-round (sync) / per-event (async) device-plane sample.

    Sync-engine semantics in parentheses where the async meaning differs;
    fields an engine cannot measure are 0 (e.g. staleness is identically
    0 for synchronous rounds, hop counts are 0 for always-up methods)."""
    cohort_size: Any      # () i32 clients that trained this round/event
    accepted: Any         # () i32 updates accepted into aggregation
    #                       (sync: participating members; async: cohort
    #                       members whose upload route existed)
    cluster_fill: Any     # (K,) f32 async: per-cluster buffer occupancy
    #                       after contributions; sync: members per cluster
    stale_min: Any        # () f32 staleness tau of accepted updates
    stale_mean: Any       # () f32 (all 0.0 for sync rounds)
    stale_max: Any        # () f32
    flushes: Any          # () i32 cluster buffer flushes this event
    #                       (sync: K — stage-1 aggregates every round)
    did_global: Any       # () i32 stage-2 aggregation fired
    reclustered: Any      # () i32 re-cluster event fired (sync only)
    bits_stage1: Any      # () f32 simulated intra-cluster traffic (model
    #                       up + broadcast back; c-fedavg: raw-data bits)
    bits_stage2: Any      # () f32 simulated stage-2 traffic (PS<->GS, or
    #                       the all-to-all PS consensus exchange)
    t_round_s: Any        # () f32 simulated duration of this round/event
    e_compute_j: Any      # () f32 local-compute energy this round
    e_comm_j: Any         # () f32 everything else (uplinks, routes,
    #                       stage-2 exchange): e_total - e_compute, exact
    hops_mean: Any        # () f32 mean ISL hops member->PS over reachable
    #                       participants (0.0 for always-up strategies)
    hops_max: Any         # () f32


def rounds_from_scan(telem: Telemetry) -> Dict[str, np.ndarray]:
    """Fetched per-round series keyed by field name: scalars become
    ``(R,)`` arrays, ``cluster_fill`` a ``(R, K)`` array."""
    import jax
    telem = jax.device_get(telem)
    return {name: np.asarray(getattr(telem, name))
            for name in Telemetry._fields}


@dataclass
class RunTelemetry:
    """Host-side telemetry record for one run: both planes + counters.

    ``rounds`` is the device plane (`rounds_from_scan`); ``spans`` the
    host plane (`obs.trace.Tracer.span_dicts`: name/ts_us/dur_us/depth);
    ``counters`` the per-run cache hit/miss deltas."""
    rounds: Dict[str, np.ndarray] = field(default_factory=dict)
    spans: List[Dict[str, Any]] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)

    # ---- JSON round-trip (rides RunResult.save/load) -------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "rounds": {k: np.asarray(v).tolist()
                       for k, v in self.rounds.items()},
            "spans": self.spans,
            "counters": self.counters,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunTelemetry":
        return cls(
            rounds={k: np.asarray(v) for k, v in d.get("rounds", {}).items()},
            spans=list(d.get("spans", [])),
            counters=dict(d.get("counters", {})),
        )

    @property
    def num_rounds(self) -> int:
        for v in self.rounds.values():
            return int(np.asarray(v).shape[0])
        return 0

    def phase_times(self) -> Dict[str, float]:
        """Top-level host span name -> total seconds (depth-0 spans)."""
        out: Dict[str, float] = {}
        for s in self.spans:
            if s.get("depth", 0) == 0:
                out[s["name"]] = out.get(s["name"], 0.0) + s["dur_us"] / 1e6
        return out

    def summary(self) -> str:
        """One-line digest for quickstarts and logs."""
        r = self.rounds
        bits = ["telemetry:"]
        if r:
            n = self.num_rounds
            coh = np.asarray(r["cohort_size"], np.float64)
            acc = np.asarray(r["accepted"], np.float64)
            st = np.asarray(r["stale_mean"], np.float64)
            e_c = float(np.sum(r["e_compute_j"]))
            e_m = float(np.sum(r["e_comm_j"]))
            mb = float(np.sum(r["bits_stage1"]) + np.sum(r["bits_stage2"])) / 8e6
            tot = max(e_c + e_m, 1e-12)
            bits.append(
                f"{n} rounds | cohort {coh.mean():.1f} "
                f"(accepted {acc.mean():.1f}) | stale mean {st.mean():.2f} | "
                f"{int(np.sum(r['did_global']))} globals | {mb:.2f} MB | "
                f"energy {100 * e_c / tot:.0f}% compute / "
                f"{100 * e_m / tot:.0f}% comm")
        if self.spans:
            wall = sum(s["dur_us"] for s in self.spans
                       if s.get("depth", 0) == 0) / 1e6
            bits.append(f"| {len(self.spans)} host spans ({wall:.2f}s)")
        return " ".join(bits)

    # ---- Perfetto export ----------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (open in https://ui.perfetto.dev).

        Two tracks: pid 1 = host wall-clock spans (``X`` complete
        events), pid 2 = the simulated timeline — per-round counter
        (``C``) events placed at the *simulated* time of each round, so
        cohort/staleness/energy read as time series against the
        constellation clock."""
        events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "host (wall clock)"}},
            {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
             "args": {"name": "simulated constellation clock"}},
        ]
        for s in self.spans:
            events.append({"name": s["name"], "ph": "X", "pid": 1,
                           "tid": 1, "ts": s["ts_us"], "dur": s["dur_us"],
                           "args": s.get("args", {})})
        if self.counters:
            events.append({"name": "cache_counters", "ph": "I", "pid": 1,
                           "tid": 1, "ts": 0.0, "s": "g",
                           "args": {k: int(v)
                                    for k, v in self.counters.items()}})
        r = self.rounds
        if r:
            t = np.cumsum(np.asarray(r["t_round_s"], np.float64))
            series = {
                "cohort": ("cohort_size", "accepted"),
                "staleness": ("stale_mean", "stale_max"),
                "energy_j": ("e_compute_j", "e_comm_j"),
                "traffic_bits": ("bits_stage1", "bits_stage2"),
                "hops": ("hops_mean", "hops_max"),
            }
            for name, keys in series.items():
                for i, ts in enumerate(t):
                    events.append({
                        "name": name, "ph": "C", "pid": 2, "tid": 0,
                        "ts": float(ts) * 1e6,
                        "args": {k: float(np.asarray(r[k])[i])
                                 for k in keys}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save_chrome_trace(self, path: str) -> None:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def load_chrome_trace(path: str) -> Dict[str, Any]:
    """Parse-and-validate helper (used by the CI smoke + tests)."""
    with open(path) as f:
        d = json.load(f)
    evs = d.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        raise ValueError(f"{path}: no traceEvents — not a Chrome trace")
    for e in evs:
        if "ph" not in e or "pid" not in e:
            raise ValueError(f"{path}: malformed trace event {e!r}")
    return d
