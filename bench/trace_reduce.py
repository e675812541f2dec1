"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
(Chrome trace format, times in microseconds on one clock for host and
device).  On a TPU each chip is a process named ``/device:TPU:<i>``; its
``XLA Ops`` thread holds one event per executed HLO operation, with the
operation's named-scope path in ``args.tf_op`` (``.../fed_step/local_train/
...``) and its kind in ``args.hlo_category``.  ``while`` and
``conditional`` events span the operations of their bodies, so only the
other (leaf) operations count as work.  Host threads carry the runtime's
spans and the harness's ``TraceAnnotation`` spans (``bench/...``).

`reduce` gives, inside the host span named ``window``:

* ``busy_s``: the union of the leaf operations' intervals, averaged over
  the chips; ``window_s``: the span's length;
* ``scope_s``: leaf-operation seconds per named scope (the first path of
  a fused operation's ``tf_op``), summed over chips and divided by their
  number;
* ``collective_s``: the largest over chips of its collective operations'
  seconds;
* ``top_ops``: the ten leaf operations (by name without the instance
  number) that took most device time, per chip;
* ``idle_gaps``: of the 50 longest stretches with no leaf operation on
  chip 0, the seconds under each pair of innermost host spans (the
  stretches are cut where host spans begin or end), the ten largest.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

CONTAINERS = {"while", "conditional", "call"}
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def load(trace_dir: str) -> dict:
    """The Chrome trace a ``jax.profiler`` trace directory holds."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not found:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    return load_file(found[-1])


def load_file(path: str) -> dict:
    with gzip.open(path) as f:
        return json.load(f)


def _union(intervals):
    total, cur = 0.0, None
    for s, e in sorted(intervals):
        if cur is None or s > cur[1]:
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    if cur is not None:
        total += cur[1] - cur[0]
    return total


def _gaps(intervals, lo, hi):
    """Stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _kind(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def reduce(trace: dict, window: str = "bench/window",
           devices: int = 1) -> dict:
    events = trace["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    chips = sorted(p for p, n in procs.items()
                   if re.fullmatch(r"/device:TPU:\d+", n))[:devices]
    host = [p for p, n in procs.items() if n.startswith("/host:")]
    spans = [e for e in events if e.get("ph") == "X" and e["pid"] in host
             and "ts" in e]
    win = [e for e in spans if e["name"] == window]
    if not win:
        raise ValueError(f"no host span named {window!r} in the trace")
    lo = win[0]["ts"]
    hi = lo + win[0]["dur"]

    busy, scope, coll, ops = [], collections.Counter(), [], \
        collections.Counter()
    leaf0 = []
    for pid in chips:
        leaves, c_s = [], 0.0
        for e in events:
            if (e.get("ph") != "X" or e["pid"] != pid or "ts" not in e
                    or threads.get((pid, e["tid"])) != "XLA Ops"):
                continue
            a = e.get("args", {})
            if a.get("hlo_category") in CONTAINERS:
                continue
            s, t = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
            if t <= s:
                continue
            leaves.append((s, t))
            d = (t - s) * 1e-6
            scope[a.get("tf_op", "").split(";")[0]] += d / len(chips)
            ops[_kind(e["name"])] += d / len(chips)
            if COLLECTIVE.match(e["name"]):
                c_s += d
        busy.append(_union(leaves) * 1e-6)
        coll.append(c_s)
        if pid == chips[0]:
            leaf0 = leaves

    named = collections.Counter()
    for s, t in sorted(_gaps(leaf0, lo, hi), key=lambda g: g[0] - g[1])[:50]:
        over = [e for e in spans if e["name"] != window
                and e["ts"] < t and e["ts"] + e["dur"] > s]
        cuts = sorted({s, t} | {x for e in over
                                for x in (e["ts"], e["ts"] + e["dur"])
                                if s < x < t})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = sorted((e for e in over
                            if e["ts"] <= mid <= e["ts"] + e["dur"]),
                           key=lambda e: e["dur"])
            name = " < ".join(dict.fromkeys(e["name"] for e in cover[:2]))
            named[name or "no host span"] += (b - a) * 1e-6
    return {
        "window_s": (hi - lo) * 1e-6,
        "busy_s": sum(busy) / max(len(busy), 1),
        "chips": len(chips),
        "scope_s": dict(scope),
        "collective_s": max(coll) if coll else 0.0,
        "top_ops": [[k, v] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v] for k, v in named.most_common(10)],
    }


def scope_seconds(red: dict, scope: str) -> float:
    """Device seconds of the operations under a named scope."""
    return sum(v for k, v in red["scope_s"].items()
               if f"/{scope}/" in f"/{k}/")
