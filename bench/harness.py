"""One benchmark run of one cell, driven by the files under ``bench/``.

A cell (an entry of ``BENCHMARK.json`` ``workloads``) names a
configuration (``bench/configs/<config>.json``: a ``Scenario`` body, its
source and cuts, the constellation geometry) and a traffic mix
(``bench/traffic/<traffic>.json``: the method, rounds or events per
call, eval cadence, async cohort and buffer).  The traffic's method
names its plain reference, ``bench/reference/<method>.py``; a method
with no reference file is an error.  The cell's correctness limits are
in ``bench/limits/<workload>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``, or, where no such file is, by the reader
named by the part of the metric's name before its first dot (so
``mfu.sync`` and ``mfu.async`` share ``mfu.py``).  Adding any of these
is adding a file.

A run: set-up (imports, device, compile cache, one warm-up
``repro.api.run`` call with a caller-owned setup cache, then the
benchmark's own inputs made from the seed and handed to that cache);
the window (``api.run`` again and again until ``seconds`` have passed,
each call ending in its own fetch of the history); then the comparison
of every call's history with the plain reference on the same inputs.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(BENCH, ".work")          # traces; in .gitignore


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------- files
def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, base: str = None):
    """(benchmark, cell, config, traffic, limits) for a workload name;
    ``base`` (the tests give their own) holds a ``BENCHMARK.json`` and
    the ``configs``, ``traffic`` and ``limits`` it names."""
    bench = _json(os.path.join(base, "BENCHMARK.json") if base
                  else os.path.join(ROOT, "BENCHMARK.json"))
    base = base or BENCH
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    config = _json(base, "configs", cell["config"] + ".json")
    traffic = _json(base, "traffic", cell["traffic"] + ".json")
    limits = _json(base, "limits", workload + ".json")
    return bench, cell, config, traffic, limits


def scenario_dict(config: dict, traffic: dict, seed: int,
                  telemetry: bool) -> dict:
    """The ``Scenario.to_dict()`` body of this cell: the configuration's,
    with the traffic's method, rounds, eval cadence and async knobs."""
    d = json.loads(json.dumps(config["scenario"]))
    d["method"] = traffic["method"]
    d["seed"] = seed
    d["train"]["rounds"] = traffic["rounds"]
    d["train"]["eval_every"] = traffic["eval_every"]
    d["async_"].update(traffic.get("async", {}))
    d["exec"]["telemetry"] = telemetry
    return d


def shape_of(config: dict) -> dict:
    """The sizes the FLOP counters take."""
    s = config["scenario"]
    ds = s["data"]["dataset"]
    return {"img": ds["img"], "ch": ds["channels"],
            "classes": ds["num_classes"],
            "clients": s["fleet"]["num_clients"],
            "local_steps": s["train"]["local_steps"],
            "batch_size": s["train"]["batch_size"],
            "eval_size": s["data"]["eval_size"]}


def spec_of(config: dict, traffic: dict):
    from reference import common as ref
    s = config["scenario"]
    ds, tr, fl = s["data"]["dataset"], s["train"], s["fleet"]
    g = config["geometry"]
    a = traffic.get("async", {})
    return ref.Spec(
        n=fl["num_clients"], k=fl["num_clusters"], planes=g["planes"],
        sats_per_plane=g["sats_per_plane"], img=ds["img"],
        ch=ds["channels"], classes=ds["num_classes"],
        spc=s["data"]["samples_per_client"], batch=tr["batch_size"],
        steps=tr["local_steps"], lr=tr["lr"], m=tr["rounds_per_global"],
        z=fl["dropout_threshold"], alpha=tr["maml_alpha"],
        beta=tr["maml_beta"], round_minutes=fl["round_minutes"],
        rounds=traffic["rounds"], altitude_km=g["altitude_km"],
        inclination_deg=g["inclination_deg"], phasing=g["phasing"],
        cohort=a.get("cohort", 0), buffer=a.get("buffer", 0),
        staleness_a=s["async_"]["staleness_a"],
        block=config["reference_block"])


def _load(path: str, name: str):
    """The module of a file under ``bench/``, loaded once."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def method_module(traffic: dict):
    """The plain reference of the traffic's method."""
    path = os.path.join(BENCH, "reference", traffic["method"] + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no reference for method {traffic['method']!r}: "
                         f"bench/reference/{traffic['method']}.py is "
                         f"missing")
    return _load(path, "bench_reference_"
                 + traffic["method"].replace("-", "_"))


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the reader of the name's part
    before its first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            return _load(path, "bench_metric_" + stem.replace(".", "_"))
    raise SystemExit(f"no reader for metric {name!r} in bench/metrics/")


def flops_per_unit(config: dict, traffic: dict) -> int:
    mod = _load(os.path.join(BENCH, "flops", config["flops"] + ".py"),
                "bench_flops_" + config["flops"])
    return mod.per_unit(shape_of(config), traffic)


def peak_flops(device_kind: str) -> float:
    peaks = _json(BENCH, "peaks.json")["chips"]
    if device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return float(peaks[device_kind]["bf16_flops"])


# ------------------------------------------------------------ comparison
def history(result) -> dict:
    """The compared part of a ``RunResult``."""
    h = {"round": [int(r) for r in result.round],
         "loss": [float(x) for x in result.loss],
         "acc": [float(x) for x in result.acc],
         "time_s": [float(x) for x in result.time_s],
         "energy_j": [float(x) for x in result.energy_j],
         "reclusters": int(result.reclusters),
         "global_rounds": int(result.global_rounds)}
    if result.flushes is not None:
        h["flushes"] = int(result.flushes)
    return h


def _worst(values):
    """Largest value; a NaN anywhere reads as infinitely far off."""
    values = [float(v) for v in values]
    return float("inf") if any(v != v for v in values) else max(values)


def _rel(a, b):
    return _worst(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def eval_rounds(traffic: dict) -> list:
    """The 1-based rounds (or events) at which the program records its
    history: every ``eval_every``-th and the last."""
    n, every = traffic["rounds"], traffic["eval_every"]
    return [r for r in range(1, n + 1) if r % every == 0 or r == n]


def at_rounds(hist: dict, rounds: list) -> dict:
    """A history of every round cut to the recorded ``rounds``."""
    return dict(hist, round=list(rounds),
                **{key: [hist[key][r - 1] for r in rounds]
                   for key in ("loss", "acc", "time_s", "energy_j")})


def gaps(got: dict, want: dict, rounds: list) -> dict:
    """The numbers compared, worst over the recorded ``rounds``: relative
    gaps of the loss, simulated time and energy; absolute gap of the
    accuracy (a share of the test set); differences of the event counts
    over the whole call.  ``want`` holds every round."""
    if list(got["round"]) != list(rounds):
        return {"rounds_missing": len(set(got["round"]) ^ set(rounds))}
    want = at_rounds(want, rounds)
    g = {"loss_gap": _rel(got["loss"], want["loss"]),
         "acc_gap": _worst(abs(x - y) for x, y in zip(got["acc"],
                                                      want["acc"])),
         "time_gap": _rel(got["time_s"], want["time_s"]),
         "energy_gap": _rel(got["energy_j"], want["energy_j"]),
         "reclusters_diff": abs(got["reclusters"] - want["reclusters"]),
         "global_diff": abs(got["global_rounds"] - want["global_rounds"])}
    if "flushes" in want:
        g["flushes_diff"] = abs(got.get("flushes", -1) - want["flushes"])
    return g


def judge(gap_list, limits: dict):
    """(correct, failed calls, {number: [worst, limit]})."""
    worst, failed = {}, 0
    for g in gap_list:
        bad = False
        for key, val in g.items():
            lim = limits.get(key, 0.0)
            worst[key] = max(worst.get(key, 0.0), val)
            bad |= not val <= lim
        failed += int(bad)
    checks = {k: [v, limits.get(k, 0.0)] for k, v in worst.items()}
    return failed == 0 and bool(gap_list), failed, checks


def run_reference(spec, config: dict, traffic: dict, seed: int,
                  precision: str = "stated", fault: str = None) -> dict:
    import inputs
    import jax
    inp = inputs.make(spec, config["scenario"]["data"], seed)
    method = method_module(traffic)
    with jax.profiler.TraceAnnotation("bench/reference"):
        return method.run(spec, inp, precision=precision, fault=fault)


# -------------------------------------------------------------- the run
def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def _memory_peak(devs) -> int:
    """Peak bytes on the fullest chip: the larger of the peak of live
    buffers and the peak reserved (a TPU keeps program scratch there)."""
    best = 0
    for d in devs:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("peak_bytes_reserved", 0)))
    return best


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             base: str = None, program_fault=None) -> dict:
    """One run; returns the result line as a dict.  ``program_fault`` (a
    context manager factory) is for the tests: it breaks the program
    underneath the timed path."""
    bench, cell, config, traffic, limits = load_cell(workload, base)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    devs = _devices(cell["chips"], require_chip)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import api
    import inputs

    scen = api.Scenario.from_dict(scenario_dict(config, traffic, seed,
                                                telemetry=trace))
    spec = spec_of(config, traffic)
    method = method_module(traffic)
    cache = {}
    with program_fault() if program_fault else contextlib.nullcontext():
        t_first = time.perf_counter()
        first = api.run(scen, setup_cache=cache)
        t_inputs = time.perf_counter()
        (key, entry), = cache.items()
        cache[key] = inputs.to_program(
            spec, inputs.make(spec, config["scenario"]["data"], seed), entry,
            method)
        del entry
        jax.block_until_ready(cache[key])
        setup_s = time.perf_counter() - t_start
        split = {"imports_init_s": t_first - t_start,
                 "engine_setup_s": first.setup_s,
                 "compile_s": first.compile_s,
                 "warmup_run_s": first.run_s,
                 "inputs_s": setup_s - (t_inputs - t_start)}

        trace_dir = None
        if trace:
            trace_dir = os.path.join(WORK, f"trace-{workload}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        results, calls = [], 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/window"):
            while True:
                with jax.profiler.TraceAnnotation("bench/api.run"):
                    r = api.run(scen, setup_cache=cache)
                with jax.profiler.TraceAnnotation("bench/history"):
                    results.append(history(r))
                calls += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    mem_peak = _memory_peak(devs)
    engine_setup_s, compile_s = first.setup_s, first.compile_s
    del r, first
    cache.clear()
    gc.collect()

    want = run_reference(spec, config, traffic, seed)
    correct, failed, checks = judge(
        [gaps(h, want, eval_rounds(traffic)) for h in results], limits)

    units = calls * traffic["rounds"]
    kind = devs[0].device_kind
    out = {"correct": correct, "attempted": calls, "failed": failed}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    metrics = {}
    if not trace:
        values = {"setup_s": setup_s, traffic["time_metric"]:
                  window_s / units}
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        import trace_reduce
        red = trace_reduce.reduce(trace_reduce.load(trace_dir),
                                  window="bench/window",
                                  devices=len(devs))
        ctx = {"trace": red, "window_s": window_s, "units": units,
               "chips": len(devs), "memory_peak_bytes": mem_peak,
               "engine_setup_s": engine_setup_s, "compile_s": compile_s,
               "flops_per_unit": flops_per_unit(config, traffic),
               "peak_flops": (peak_flops(kind) if require_chip else None)}
        reported = {m["name"] for m in bench["end_to_end"]
                    if workload in m.get("workloads", [workload])}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            if m["moves"] not in reported:
                continue
            val = metric_reader(m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["metrics"] = metrics
    out["device"] = device
    out["setup_split"] = split
    for name, (val, lim) in checks.items():
        print(f"check {name} {val!r} limit {lim!r}", file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out
