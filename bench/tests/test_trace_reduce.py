"""The reduction from a profiler trace to per-layer numbers.

A hand-made trace pins the arithmetic (union of intervals, containers
left out, collectives, gaps named by the host span over them).  A small
trace recorded on a TPU v5e pins how a real one reads: the chip is the
process ``/device:TPU:0``, its operations are on the ``XLA Ops`` thread
with the named-scope path in ``args.tf_op``
(``jit(run_scan)/while/body/closed_call/fed_step/local_train/...``).  It
is one ``repro.api.run`` call of fedhc at N=32, 2 rounds, blocks of 16,
telemetry on, inside a host span ``bench/call``.
"""
import os

import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_n32_sync.trace.json.gz")


def _op(ts, dur, name, scope="", cat="loop fusion", pid=3):
    return {"ph": "X", "pid": pid, "tid": 3, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": scope, "hlo_category": cat}}


def _made():
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    host = [
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 100,
         "name": "bench/window"},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 60,
         "name": "bench/api.run"},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 60, "dur": 40,
         "name": "bench/history"},
    ]
    ops = [
        _op(0, 50, "while.1", cat="while"),            # a container
        _op(0, 10, "fusion.1", "jit(f)/fed_step/local_train/dot"),
        _op(5, 10, "fusion.2", "jit(f)/fed_step/local_train/dot"),
        _op(20, 10, "fusion.3", "jit(f)/fed_step/aggregate/dot"),
        _op(30, 5, "all-reduce.7", "jit(f)/fed_step/aggregate/x",
            cat="collective"),
        _op(90, 20, "copy.4", "jit(f)/copy"),           # cut at 100
    ]
    return {"traceEvents": meta + host + ops}


def test_made_trace():
    red = trace_reduce.reduce(_made())
    assert red["window_s"] == pytest.approx(100e-6)
    # [0,15] + [20,35] + [90,100]
    assert red["busy_s"] == pytest.approx(40e-6)
    assert trace_reduce.scope_seconds(red, "fed_step/local_train") == \
        pytest.approx(20e-6)
    assert trace_reduce.scope_seconds(red, "fed_step/aggregate") == \
        pytest.approx(15e-6)
    assert red["collective_s"] == pytest.approx(5e-6)
    assert dict(red["top_ops"])["fusion"] == pytest.approx(30e-6)
    assert "while" not in dict(red["top_ops"])
    gaps = dict(red["idle_gaps"])
    assert gaps["bench/history"] == pytest.approx(30e-6)      # [60,90]
    assert gaps["bench/api.run"] == pytest.approx(30e-6)      # [15,20]+[35,60]



def test_recorded_tpu_trace():
    red = trace_reduce.reduce(trace_reduce.load_file(RECORDED),
                              window="bench/call")
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(0.056649048)
    assert red["busy_s"] == pytest.approx(0.0500081327)
    train = trace_reduce.scope_seconds(red, "fed_step/local_train")
    agg = trace_reduce.scope_seconds(red, "fed_step/aggregate")
    assert train == pytest.approx(0.0489148434)
    assert agg == pytest.approx(0.000119719376)
    assert red["collective_s"] == 0.0
    assert sum(v for _, v in red["top_ops"]) <= red["busy_s"]
    assert len(red["top_ops"]) == len(red["idle_gaps"]) == 10
