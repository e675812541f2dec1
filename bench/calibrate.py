#!/usr/bin/env python3
"""Readings the correctness limits are set from, on the chip.

    python bench/calibrate.py --workload mnist-n800.sync \\
        --seeds 11 12 13 --controls 11 12 13 --faults 11

One process, one compile: for each seed of ``--seeds`` the cell's inputs
are made from the seed, handed to ``repro.api.run`` through its setup
cache (as a benchmark run does), and its history is compared with the
plain reference's: the lower readings.  For the seeds of ``--controls``
the reference computed one precision step down (bfloat16, the control)
is compared with the reference; for those of ``--faults`` the reference
with each planted fault is.  One JSON line per comparison; the limits in
``bench/limits/<workload>.json`` lie between the largest sound reading
and the smallest control or fault reading.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

FAULTS = ("unchanged", "half_batch", "answer", "cost")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", type=int, nargs="*", default=[])
    ap.add_argument("--faults", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    bench, cell, config, traffic, limits = harness.load_cell(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    try:
        harness._devices(cell["chips"], True)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro import api
    import inputs
    spec = harness.spec_of(config, traffic)
    method = harness.method_module(traffic)
    rounds = harness.eval_rounds(traffic)

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    cache = {}
    if args.seeds:
        # one scenario, so every call hits the one setup-cache entry
        # whose inputs are swapped for each seed's
        scen = api.Scenario.from_dict(harness.scenario_dict(
            config, traffic, args.seeds[0], telemetry=False))
        api.run(scen, setup_cache=cache)
    for seed in args.seeds:
        (key, entry), = cache.items()
        cache[key] = inputs.to_program(
            spec, inputs.make(spec, config["scenario"]["data"], seed), entry,
            method)
        del entry
        t0 = time.perf_counter()
        got = harness.history(api.run(scen, setup_cache=cache))
        t1 = time.perf_counter()
        want = harness.run_reference(spec, config, traffic, seed)
        t2 = time.perf_counter()
        emit(kind="program", seed=seed, gaps=harness.gaps(got, want, rounds),
             program_s=t1 - t0, reference_s=t2 - t1, got=got, want=want)
    cache.clear()
    for seed in sorted(set(args.controls) | set(args.faults)):
        want = harness.run_reference(spec, config, traffic, seed)
        runs = ([("control", "bf16", None)] if seed in args.controls
                else [])
        runs += ([("fault", "stated", f) for f in FAULTS]
                 if seed in args.faults else [])
        for kind, precision, fault in runs:
            t0 = time.perf_counter()
            got = harness.run_reference(spec, config, traffic, seed,
                                        precision=precision, fault=fault)
            emit(kind=kind, fault=fault, seed=seed,
                 gaps=harness.gaps(harness.at_rounds(got, rounds), want,
                                  rounds),
                 seconds=time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
