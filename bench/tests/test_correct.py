"""The correctness check fails what it must fail, at a test size.

The harness drives a whole run of a 16-satellite cell (set-up, window,
comparison with the reference) with the look for a chip skipped: sound,
it comes out correct; with the timed path broken underneath in each way
the cell can be broken, it comes out not correct.  The control (the
reference one precision step down, in the program's place) fails the
limits of the benchmark cell the test cell stands for.
"""
import contextlib
import os
import time

import pytest

import harness

BASE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the benchmark cell whose limits each test cell's control must fail
STANDS_FOR = {"tiny.sync": "mnist-n800.sync", "tiny.async": "mnist-n800.async"}


def _clear():
    from repro import api
    from repro.core import async_engine, engine
    engine._scan_fn_cached.cache_clear()
    async_engine._scan_fn_cached.cache_clear()
    api._COMPILED.clear()


def _unchanged(orig):
    def local_train(params, images, labels, **kw):
        _, losses = orig(params, images, labels, **kw)
        return params, losses
    return local_train


def _half_batch(orig):
    def local_train(params, images, labels, **kw):
        half = images.shape[1] // 2
        return orig(params, images[:, :half], labels[:, :half], **kw)
    return local_train


def _answer(orig):
    def accuracy(params, images, labels):
        return orig(params, images, (labels + 1) % 10)
    return accuracy


def _cost(orig):
    def cluster_member_costs(positions, ps_positions, data_sizes, freqs,
                             model_bits, *args, **kw):
        return orig(positions, ps_positions, data_sizes, freqs,
                    model_bits / 2, *args, **kw)
    return cluster_member_costs


FAULTS = {"unchanged": ("_local_train", _unchanged),
          "half_batch": ("_local_train", _half_batch),
          "answer": ("lenet_accuracy", _answer),
          "cost": ("cluster_member_costs", _cost)}


def _fault(name):
    attr, wrap = FAULTS[name]

    @contextlib.contextmanager
    def ctx():
        from repro.core import async_engine, engine
        from repro.orbits import cost
        saved = [(m, getattr(m, attr)) for m in (engine, async_engine, cost)
                 if hasattr(m, attr)]
        _clear()
        for mod, orig in saved:
            setattr(mod, attr, wrap(orig))
        try:
            yield
        finally:
            for mod, orig in saved:
                setattr(mod, attr, orig)
            _clear()
    return ctx


def _run(workload, seed, fault=None):
    return harness.run_cell(workload, seed, 0.01, False,
                            t_start=time.perf_counter(), require_chip=False,
                            base=BASE,
                            program_fault=_fault(fault) if fault else None)


@pytest.mark.parametrize("workload", ["tiny.sync", "tiny.async"])
def test_sound_run_is_correct(workload):
    out = _run(workload, 2147483659)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["tiny.sync", "tiny.async"])
def test_broken_program_is_not_correct(workload, fault):
    out = _run(workload, 2147483659, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["tiny.sync", "tiny.async"])
def test_control_in_the_programs_place_is_not_correct(workload):
    bench, cell, config, traffic, _ = harness.load_cell(workload, BASE)
    limits = harness.load_cell(STANDS_FOR[workload])[-1]
    spec = harness.spec_of(config, traffic)
    want = harness.run_reference(spec, config, traffic, 7)
    control = harness.run_reference(spec, config, traffic, 7,
                                    precision="bf16")
    rounds = harness.eval_rounds(traffic)
    correct, _, checks = harness.judge(
        [harness.gaps(harness.at_rounds(control, rounds), want, rounds)],
        limits)
    assert not correct, checks
