"""The correctness check on the 3-channel path, at a test size.

The same checks as ``test_correct.py``, on a 16-satellite cell at CIFAR
geometry (32x32x3, the cifar-like generator's template and noise scales,
10 rounds a call so that stage-2 and the re-cluster check run): sound,
the run comes out correct; with the timed path broken underneath in each
planted way, it does not.  The control (the reference in bfloat16, in
the program's place) fails the limits of ``cifar-n800.sync``, the
benchmark cell this one stands for.

The cell has a ``BENCHMARK.json`` of its own under ``data/cifar``, beside
its configuration, traffic and limits.
"""
import os
import time

import pytest

import harness
from test_correct import FAULTS, _fault

BASE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cifar")
WORKLOAD = "tiny-cifar.sync"
STANDS_FOR = "cifar-n800.sync"


def _run(seed, fault=None):
    return harness.run_cell(WORKLOAD, seed, 0.01, False,
                            t_start=time.perf_counter(), require_chip=False,
                            base=BASE,
                            program_fault=_fault(fault) if fault else None)


def test_sound_cifar_run_is_correct():
    out = _run(2147483659)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_cifar_program_is_not_correct(fault):
    out = _run(2147483659, fault)
    assert not out["correct"], out["checks"]


def test_cifar_control_in_the_programs_place_is_not_correct():
    bench, cell, config, traffic, _ = harness.load_cell(WORKLOAD, BASE)
    limits = harness.load_cell(STANDS_FOR)[-1]
    spec = harness.spec_of(config, traffic)
    want = harness.run_reference(spec, config, traffic, 7)
    control = harness.run_reference(spec, config, traffic, 7,
                                    precision="bf16")
    rounds = harness.eval_rounds(traffic)
    correct, _, checks = harness.judge(
        [harness.gaps(harness.at_rounds(control, rounds), want, rounds)],
        limits)
    assert not correct, checks
