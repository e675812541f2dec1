"""Observability subsystem (repro/obs): the hard invariant — telemetry
OFF is bit-identical to the pre-obs engines, telemetry ON changes
*outputs only*, never the trajectory — plus both planes' plumbing:
per-round device series shapes/semantics, the energy split
reconciliation, JSON round-trip through RunResult.save/load, the span
tracer, cache counters, the report CLI, and Chrome trace export."""
import collections
import json
import re

import numpy as np
import pytest

from repro import api
from repro.api import ExecSpec, RunResult, Scenario
from repro.core.fedhc import FLRunConfig
from repro.data.synthetic import CIFAR_LIKE
from repro.obs.telemetry import (RunTelemetry, Telemetry,
                                 load_chrome_trace)
from repro.obs.trace import COUNTERS, Counters, Tracer


def _flat(method, **kw):
    base = dict(method=method, num_clients=12, num_clusters=2, rounds=4,
                eval_every=2, samples_per_client=16, local_steps=1,
                batch_size=8, eval_size=64)
    base.update(kw)
    return FLRunConfig(**base)


def _pair(method, **kw):
    """(telemetry-off result, telemetry-on result) sharing one setup."""
    sc = Scenario.from_flat(_flat(method, **kw))
    cache = {}
    off = api.run(sc.replace(exec=ExecSpec(telemetry=False)),
                  setup_cache=cache)
    on = api.run(sc.replace(exec=ExecSpec(telemetry=True)),
                 setup_cache=cache)
    return off, on


@pytest.fixture(scope="module")
def fedhc_pair():
    return _pair("fedhc")


@pytest.fixture(scope="module")
def async_pair():
    return _pair("fedhc-async", async_cohort=4, async_buffer=3)


@pytest.fixture(scope="module")
def fedspace_on():
    return _pair("fedspace", rounds=6, eval_every=3)[1]


# ---- the hard invariant ---------------------------------------------------


def test_sync_on_off_bit_identical(fedhc_pair):
    off, on = fedhc_pair
    assert off.to_history() == on.to_history()      # exact, not allclose
    assert off.telemetry is None                    # off: no record at all
    assert on.telemetry is not None


def test_async_on_off_bit_identical(async_pair):
    off, on = async_pair
    assert off.to_history() == on.to_history()
    t = on.telemetry.rounds
    # accepted <= cohort, staleness ordered min <= mean <= max, all >= 0
    assert (t["accepted"] <= t["cohort_size"]).all()
    assert (t["stale_min"] >= 0).all()
    assert (t["stale_min"] <= t["stale_mean"] + 1e-6).all()
    assert (t["stale_mean"] <= t["stale_max"] + 1e-6).all()


def test_exec_spec_default_off():
    assert ExecSpec().telemetry is False
    assert Scenario.from_flat(_flat("fedhc")).to_flat().telemetry is False


# ---- device-plane series semantics ---------------------------------------


def test_round_series_shapes_and_keys(fedhc_pair):
    _, on = fedhc_pair
    t = on.telemetry
    assert set(t.rounds) == set(Telemetry._fields)
    R, K = 4, 2
    for name in Telemetry._fields:
        want = (R, K) if name == "cluster_fill" else (R,)
        assert t.rounds[name].shape == want, name
    assert t.num_rounds == R
    # sync conventions: staleness identically 0, stage-1 flush = K
    assert (t.rounds["stale_max"] == 0).all()
    assert (t.rounds["flushes"] == K).all()
    assert (t.rounds["cohort_size"] == 12).all()
    # members per cluster sum to the fleet
    np.testing.assert_array_equal(
        t.rounds["cluster_fill"].sum(axis=1), np.full(R, 12.0))


def test_energy_split_reconciles(fedhc_pair):
    """e_compute + e_comm is exact: per-round sums cumulate to the
    trajectory's cumulative energy at every eval point."""
    _, on = fedhc_pair
    t = on.telemetry.rounds
    cum_e = np.cumsum(t["e_compute_j"] + t["e_comm_j"])
    for r, e in zip(on.round, on.energy_j):
        np.testing.assert_allclose(cum_e[int(r) - 1], e, rtol=1e-4)
    cum_t = np.cumsum(t["t_round_s"])
    for r, s in zip(on.round, on.time_s):
        np.testing.assert_allclose(cum_t[int(r) - 1], s, rtol=1e-4)
    assert (t["e_compute_j"] > 0).all()


def test_fedspace_hop_telemetry(fedspace_on):
    """Visibility-gated routing surfaces real hop counts: finite,
    mean <= max, and not identically zero across the run."""
    t = fedspace_on.telemetry.rounds
    assert np.isfinite(t["hops_mean"]).all()
    assert (t["hops_mean"] <= t["hops_max"] + 1e-6).all()
    assert t["hops_max"].max() >= 1.0
    # visibility gating also shows up as accepted < cohort on some round
    assert (t["accepted"] <= t["cohort_size"]).all()


# ---- phase scopes in the compiled program, telemetry off -----------------
#
# The pre-optimization HLO names each op relative to the function that
# holds it; a ``call`` prefixes its callee's names with its own, while
# ``while``/``conditional`` regions keep their function's names.  Full
# names are rebuilt along the call graph from the round (event) scan's
# ``while`` body.

_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")
_CALLS = re.compile(r"\b(to_apply|body|condition|calls|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_ELEMENTWISE = {
    "abs", "add", "and", "atan2", "cbrt", "ceil", "clamp", "compare",
    "convert", "cosine", "divide", "erf", "exponential",
    "exponential-minus-one", "floor", "is-finite", "log", "log-plus-one",
    "logistic", "maximum", "minimum", "multiply", "negate", "not", "or",
    "popcnt", "power", "remainder", "round-nearest-afz",
    "round-nearest-even", "rsqrt", "select", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "sign", "sine", "sqrt",
    "subtract", "tan", "tanh", "xor"}
_COMPUTE = _ELEMENTWISE | {
    "dot", "convolution", "reduce", "gather", "scatter", "sort",
    "concatenate", "slice", "dynamic-slice", "dynamic-update-slice"}
_PHASE = re.compile(r"(^|[/(])(fed_step|async_event)/")
SYNC_SCOPES = [r"fed_step/batches/", r"fed_step/orbit/",
               r"fed_step/local_train/", r"fed_step/aggregate/",
               r"fed_step/recluster/", r"fed_step/eval/",
               r"fed_step/recluster/(.*/)?kmeans/",
               r"fed_step/recluster/(.*/)?maml/",
               r"fed_step/recluster/(.*/)?adapt/",
               r"fed_step/eval/(.*/)?lenet_conv/",
               r"fed_step/local_train/(.*[/(])?jvp\(lenet_conv\)",
               r"fed_step/local_train/(.*[/(])?"
               r"transpose\(jvp\(lenet_conv\)\)"]
ASYNC_SCOPES = [r"async_event/select/", r"async_event/batches/",
                r"async_event/local_train/", r"async_event/contribute/",
                r"async_event/aggregate/flush/",
                r"async_event/aggregate/global/",
                r"async_event/orbit/", r"async_event/eval/"]


def _scan_hlo(method, **kw):
    """Pre-optimization HLO of the telemetry-off scan at N=16, in client
    blocks of 8 (the paper-scale path), with op metadata."""
    from repro.core import async_engine, engine, strategies
    cfg = _flat(method, num_clients=16, client_microbatch=8, **kw)
    eng = async_engine if strategies.get(method).is_async else engine
    state0, data = eng.setup(cfg)
    lowered = eng._scan_fn(cfg).lower(state0, data)
    return lowered.compiler_ir("hlo").as_hlo_module().to_string()


def _scan_ops(text):
    """[(opcode, full op name, in the scan's own loop body)] of every
    instruction the scan's ``while`` body reaches."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INST.match(line)
        if cur is None or m is None:
            continue
        _, op, rest = m.groups()
        name = re.search(r'op_name="([^"]*)"', rest)
        calls = _CALLS.findall(rest)
        b = _BRANCHES.search(rest)
        if b:
            calls += [("branch", c.strip().lstrip("%"))
                      for c in b.group(1).split(",")]
        comps[cur].append((op, name.group(1).split(";")[0] if name else "",
                           calls))
    loops = [i for i in comps[entry] if i[0] == "while"]
    assert len(loops) == 1
    body = dict(loops[0][2])["body"]
    out, seen, todo = [], set(), [(body, "")]
    while todo:
        comp, prefix = todo.pop()
        if (comp, prefix) in seen:
            continue
        seen.add((comp, prefix))
        for op, name, calls in comps[comp]:
            full = "/".join(x for x in (prefix, name) if x)
            out.append((op, full, comp == body))
            for kind, callee in calls:
                if op == "call":
                    todo.append((callee, full))
                elif kind != "to_apply":      # reducers: scalar combiners
                    todo.append((callee, prefix))
    return out


@pytest.fixture(scope="module")
def scan_ops():
    return {
        "fedhc": _scan_ops(_scan_hlo("fedhc")),
        "fedhc-async": _scan_ops(_scan_hlo("fedhc-async", async_cohort=4,
                                           async_buffer=3)),
        "c-fedavg": _scan_ops(_scan_hlo("c-fedavg")),
        # the 3-channel 32x32 path: LeNet's grouped conv over 3 input
        # channels, at CIFAR widths
        "fedhc-cifar": _scan_ops(_scan_hlo("fedhc", dataset=CIFAR_LIKE)),
    }


@pytest.mark.parametrize("method, scopes", [("fedhc", SYNC_SCOPES),
                                            ("fedhc-async", ASYNC_SCOPES),
                                            ("fedhc-cifar", SYNC_SCOPES)])
def test_every_phase_scope_in_the_telemetry_off_scan(scan_ops, method,
                                                     scopes):
    names = [full for _, full, _ in scan_ops[method]]
    for scope in scopes:
        assert any(re.search(scope, n) for n in names), scope


@pytest.mark.parametrize("method", ["fedhc", "fedhc-async", "c-fedavg",
                                    "fedhc-cifar"])
def test_no_compute_op_of_the_scan_body_outside_a_phase(scan_ops, method):
    ops = scan_ops[method]
    # the scan's own loop body: its counter, its slice of the round
    # index and its updates of the stacked outputs
    own = {op for op, _, top in ops if top and op in _COMPUTE}
    assert own <= {"add", "dynamic-slice", "dynamic-update-slice"}
    outside = collections.Counter(
        (op, full) for op, full, top in ops
        if not top and op in _COMPUTE and not _PHASE.search(full))
    assert not outside, outside.most_common(10)


# ---- host plane: spans, counters, timing ---------------------------------


def test_host_spans_cover_phases(fedhc_pair):
    _, on = fedhc_pair
    spans = {s["name"]: s for s in on.telemetry.spans}
    # run ends at block_until_ready, fetch is the device_get, history the
    # numpy extraction: three spans, one after another
    assert {"run", "fetch", "history"} <= set(spans)
    assert (spans["run"]["ts_us"] + spans["run"]["dur_us"]
            <= spans["fetch"]["ts_us"])
    assert (spans["fetch"]["ts_us"] + spans["fetch"]["dur_us"]
            <= spans["history"]["ts_us"])
    for s in on.telemetry.spans:
        assert s["dur_us"] >= 0 and s["ts_us"] >= 0


def test_host_phases_annotate_with_telemetry_off(monkeypatch):
    """Every api.run phase enters a profiler annotation on the device
    trace's clock, telemetry on or off."""
    import jax.profiler
    seen = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            seen.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    res = api.run(Scenario.from_flat(_flat("h-base", rounds=2,
                                           eval_every=2)))
    assert res.telemetry is None
    assert {"api.run/setup", "api.run/run", "api.run/fetch",
            "api.run/history"} <= set(seen)
    assert (seen.index("api.run/run") < seen.index("api.run/fetch")
            < seen.index("api.run/history"))


def test_tracer_nesting_and_durations():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    spans = tr.span_dicts()
    d = {s["name"]: s for s in spans}
    assert d["outer"]["depth"] == 0 and d["inner"]["depth"] == 1
    assert d["inner"]["ts_us"] >= d["outer"]["ts_us"]
    assert d["outer"]["dur_us"] >= d["inner"]["dur_us"]
    assert tr.phase_times()["outer"] > 0


def test_counters_inc_and_delta():
    c = Counters()
    c.inc("x")
    c.inc("x", 2)
    before = c.snapshot()
    c.inc("x")
    c.inc("y")
    assert Counters.delta(before, c.snapshot()) == {"x": 1, "y": 1}


def test_timing_fields_nonnegative_both_engines(fedhc_pair, async_pair):
    for res in (*fedhc_pair, *async_pair):
        assert res.setup_s >= 0
        assert res.compile_s >= 0
        assert res.run_s > 0
        assert res.wall_s >= res.run_s


def test_setup_cache_second_call_hits():
    """Satellite pin: the second api.run against one setup_cache reuses
    the eager setup — observed through the always-on COUNTERS, no
    telemetry required."""
    sc = Scenario.from_flat(_flat("h-base", rounds=3, eval_every=3))
    cache = {}
    s0 = COUNTERS.snapshot()
    api.run(sc, setup_cache=cache)
    d1 = Counters.delta(s0, COUNTERS.snapshot())
    assert d1.get("api.setup_cache.miss") == 1
    s1 = COUNTERS.snapshot()
    r2 = api.run(sc, setup_cache=cache)
    d2 = Counters.delta(s1, COUNTERS.snapshot())
    assert d2.get("api.setup_cache.hit") == 1
    assert "api.setup_cache.miss" not in d2
    assert r2.setup_s == 0.0 or r2.setup_s < 0.05  # cached setup is ~free


def test_compile_on_hit_counts_recompiles_behind_a_cached_executable():
    """``jax.backend_compile`` counts every backend compile of the
    process; ``api.compile_on_hit`` counts the calls that hit the AOT
    cache and still compiled."""
    import jax
    c0 = COUNTERS.get("jax.backend_compile")
    jax.jit(lambda x: x * 3.0 + 1.0)(2.0)
    assert COUNTERS.get("jax.backend_compile") == c0 + 1

    sc = Scenario.from_flat(_flat("h-base", rounds=3, eval_every=3))
    api.run(sc)                         # compiles, or hits: either way
    hits0 = COUNTERS.get("api.compile_on_hit")
    s0 = COUNTERS.snapshot()
    api.run(sc)                         # AOT hit, every eager op cached
    d = Counters.delta(s0, COUNTERS.snapshot())
    assert d.get("api.aot_cache.hit") == 1
    assert "jax.backend_compile" not in d
    assert COUNTERS.get("api.compile_on_hit") == hits0

    jax.clear_caches()                  # setup's eager ops must compile
    s1 = COUNTERS.snapshot()
    api.run(sc)
    d = Counters.delta(s1, COUNTERS.snapshot())
    assert d.get("api.aot_cache.hit") == 1
    assert d.get("jax.backend_compile", 0) > 0
    assert d.get("api.compile_on_hit") == 1


def test_run_telemetry_counters_carry_every_delta():
    sc = Scenario.from_flat(_flat("h-base", rounds=2, eval_every=2))
    res = api.run(sc.replace(exec=ExecSpec(telemetry=True)))
    c = res.telemetry.counters
    assert c.get("api.aot_cache.hit", 0) + c.get("api.aot_cache.miss",
                                                 0) == 1
    if c.get("api.aot_cache.miss"):
        assert c.get("jax.backend_compile", 0) >= 1


def test_peak_device_mem_takes_reserved_bytes():
    """On a TPU the program's scratch shows only in the reserved peak."""
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    mb = 1e6
    assert api._peak_device_mem_mb([
        Dev({"peak_bytes_in_use": 2 * mb, "peak_bytes_reserved": 5 * mb}),
        Dev({"peak_bytes_in_use": 3 * mb})]) == 5.0
    assert api._peak_device_mem_mb([Dev({"peak_bytes_in_use": 2 * mb})]) \
        == 2.0
    assert api._peak_device_mem_mb([Dev(None)]) is None


def test_peak_host_mem_reported(fedhc_pair):
    off, _ = fedhc_pair
    # ru_maxrss exists on every POSIX host this repo targets
    assert off.peak_host_mem_mb is not None
    assert off.peak_host_mem_mb > 0


# ---- persistence + rendering ---------------------------------------------


def test_telemetry_save_load_roundtrip(tmp_path, fedhc_pair):
    _, on = fedhc_pair
    p = tmp_path / "run.json"
    on.save(str(p))
    back = RunResult.load(str(p))
    assert back.telemetry is not None
    for name in Telemetry._fields:
        np.testing.assert_allclose(back.telemetry.rounds[name],
                                   on.telemetry.rounds[name])
    assert back.telemetry.spans == on.telemetry.spans
    assert back.telemetry.counters == on.telemetry.counters
    assert back.peak_host_mem_mb == on.peak_host_mem_mb
    # telemetry-off results keep the old schema working
    p2 = tmp_path / "off.json"
    fedhc_pair[0].save(str(p2))
    assert RunResult.load(str(p2)).telemetry is None


def test_run_telemetry_dict_roundtrip(fedhc_pair):
    t = fedhc_pair[1].telemetry
    back = RunTelemetry.from_dict(t.to_dict())
    assert back.num_rounds == t.num_rounds
    assert json.dumps(back.to_dict()) == json.dumps(t.to_dict())


def test_chrome_trace_export(tmp_path, fedhc_pair):
    _, on = fedhc_pair
    p = tmp_path / "trace.json"
    on.telemetry.save_chrome_trace(str(p))
    d = load_chrome_trace(str(p))
    evs = d["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert "X" in phases and "C" in phases and "M" in phases
    # counter events live on the simulated-clock track (pid 2)
    assert all(e["pid"] == 2 for e in evs if e["ph"] == "C")
    assert any(e["pid"] == 1 for e in evs if e["ph"] == "X")


def test_report_cli(tmp_path, fedhc_pair, capsys):
    from repro.obs import report
    _, on = fedhc_pair
    p = tmp_path / "run.json"
    on.save(str(p))
    trace = tmp_path / "trace.json"
    assert report.main([str(p), "--trace", str(trace)]) == 0
    out = capsys.readouterr().out
    assert "round |" in out and "device plane: 4 rounds" in out
    assert "phase breakdown" in out
    load_chrome_trace(str(trace))
    # telemetry-off runs still render (no table), but --trace is an error
    p2 = tmp_path / "off.json"
    fedhc_pair[0].save(str(p2))
    assert report.main([str(p2)]) == 0
    assert "no device-plane telemetry" in capsys.readouterr().out
    assert report.main([str(p2), "--trace", str(tmp_path / "x.json")]) == 2
