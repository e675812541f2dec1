"""The readers of the per-phase metrics, on a made trace.

Each operation carries the named-scope path the program gives it
(``jit(run_scan)/while/body/closed_call/fed_step/<phase>/...``); LeNet's
patch building shows bare (eval), as ``jvp(conv_patches)`` (forward
under ``grad``) and as ``transpose(jvp(conv_patches))`` (backward), and
a copy the compiler adds has no path at all.  The readers are found by
name as the harness finds them, so ``.sync``/``.async`` pairs resolve
to the file of the name's first part and ``local_train_ms.async`` to
its own file.
"""
import pytest

import harness
import trace_reduce

BODY = "jit(run_scan)/while/body/closed_call/"
# (microseconds, tf_op) of every leaf operation in the window
OPS = [
    (3, BODY + "fed_step/batches/gather"),
    (5, BODY + "fed_step/orbit/dot_general"),
    (7, BODY + "fed_step/eval/cond/branch_1_fun/conv_patches/slice"),
    (11, BODY + "fed_step/local_train/while/body/closed_call/vmap()/while"
     "/body/closed_call/jvp(conv_patches)/slice"),
    (13, BODY + "fed_step/local_train/while/body/closed_call/vmap()/while"
     "/body/closed_call/transpose(jvp(conv_patches))/pad"),
    (17, BODY + "fed_step/recluster/cond/branch_1_fun/maml/while/body/"
     "closed_call/vmap(transpose(jvp(conv_patches)))/add_any"),
    (19, BODY + "fed_step/local_train/conv_patches_extra/add"),
    (23, BODY + "async_event/select/top_k"),
    (29, BODY + "async_event/contribute/scatter"),
    (31, BODY + "async_event/batches/gather"),
    (37, BODY + "async_event/local_train/while/body/dot_general"),
    (41, BODY + "async_event/aggregate/flush/dot_general"),
    (43, BODY + "async_event/aggregate/global/cond/branch_1_fun/mul"),
    (47, BODY + "async_event/orbit/sqrt"),
    (53, BODY + "async_event/eval/cond/branch_1_fun/conv_patches/"
     "concatenate"),
    (59, "jit(run_scan)/while/body/dynamic_update_slice"),
    (61, ""),
]
UNITS = 2


def _trace(ops):
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    host = [{"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 10_000,
             "name": "bench/window"}]
    events, t = [], 0
    for i, (dur, scope) in enumerate(ops):
        events.append({"ph": "X", "pid": 3, "tid": 3, "ts": t, "dur": dur,
                       "name": f"fusion.{i}",
                       "args": {"tf_op": scope,
                                "hlo_category": "loop fusion"}})
        t += dur
    return {"traceEvents": meta + host + events}


def _read(name, ops=OPS):
    red = trace_reduce.reduce(_trace(ops))
    return harness.metric_reader(name).read({"trace": red, "units": UNITS})


def _ms(*us):
    """Milliseconds per unit of these operations' microseconds."""
    return pytest.approx(sum(us) * 1e-3 / UNITS)


@pytest.mark.parametrize("name, want", [
    ("batches_ms.sync", _ms(3, 31)),
    ("batches_ms.async", _ms(3, 31)),
    ("orbit_ms.sync", _ms(5, 47)),
    ("eval_ms.async", _ms(7, 53)),
    ("recluster_ms", _ms(17)),
    ("local_train_ms.async", _ms(37)),
    ("aggregate_ms.async", _ms(41, 43)),
    ("cohort_ms", _ms(23, 29)),
    # the sync reader keeps reading the sync scope only
    ("local_train_ms", _ms(11, 13, 19)),
])
def test_phase_readers(name, want):
    assert _read(name) == want


def test_conv_patches_bare_and_under_transforms():
    # eval (bare), jvp, transpose(jvp), vmap(transpose(jvp)), eval async;
    # ``conv_patches_extra`` is another scope
    assert _read("conv_patches_ms.sync") == _ms(7, 11, 13, 17, 53)
    assert _read("conv_patches_ms.async") == _read("conv_patches_ms.sync")


def test_unscoped_counts_loop_bookkeeping_and_empty_tf_op():
    assert _read("unscoped_ms.sync") == _ms(59, 61)
    assert _read("unscoped_ms.async") == _ms(59, 61)


@pytest.mark.parametrize("name", [
    "batches_ms.sync", "orbit_ms.async", "eval_ms.sync",
    "conv_patches_ms.sync", "recluster_ms", "local_train_ms.async",
    "aggregate_ms.async", "cohort_ms", "unscoped_ms.sync"])
def test_reader_without_its_scope_reads_nothing(name):
    # a program that emits no such scope (a parent without this
    # instrumentation) gives no number, and no error
    fed = [(5, BODY + "fed_step/local_train/dot_general")]
    assert _read(name, fed) is None


def test_compile_on_hit_is_a_count():
    from repro.obs.trace import COUNTERS
    want = float(COUNTERS.get("api.compile_on_hit"))
    assert _read("compile_on_hit.sync") == want
    assert _read("compile_on_hit.async") == want
    COUNTERS.inc("api.compile_on_hit")
    assert _read("compile_on_hit.sync") == want + 1.0
