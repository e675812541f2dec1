"""Paper-scale engine benchmark: constellation size sweep N in {64, 256,
800} (the paper evaluates FedHC up to 800 satellites) with an N=10k
mega-constellation smoke on a forced-host client mesh.

Per N it runs the round engine twice from one cached setup — the
full-vmap local-train path and the microbatched one
(``ExecSpec.client_microbatch``) — and reports setup / compile /
per-round seconds plus the client-stack footprint.  Profiling the sweep
(``--profile``) is what motivated the variants: at N=800 local training
is ~97% of the round and superlinear in the full-vmap path (the
activation working set blows the cache); microbatching restores linear
scaling.  It also measures the contact-plan storage ladder — f32 vs bf16
tables, cluster-sliced tables, and the factorized (store-nothing,
recompute-in-scan) plan the 10k point needs.

    PYTHONPATH=src python -m benchmarks.scale_bench [options]

    --fast           drop the N=800 point (CI-sized)
    --smoke          regression gate: run the N=64 cell and fail (exit 2)
                     if per-round exceeds 2x the committed
                     results/scale_bench.json entry — CI runs this under
                     XLA_FLAGS=--xla_force_host_platform_device_count=8
    --mega           the N=10k smoke: fedspace + factorized plan +
                     microbatched train on a client mesh over all local
                     devices; merges a "mega_smoke" entry into results
    --profile DIR    wrap each timed run in jax.profiler.trace(DIR/nN);
                     open the trace with TensorBoard (or xprof) and read
                     the op-level timeline: one `scan` body per round —
                     conv_general_dilated under `local_train` is the
                     training cost, the (C,K) dots under `aggregate` the
                     aggregation cost, `route_rows` the in-scan routing
                     recompute (factorized plans only)
    --sharded-smoke  tiny sharded fedhc end-to-end parity check on a
                     client mesh (needs >1 device), prints shardings

Results land in results/scale_bench.json.  Timing semantics: setup_s /
compile_s / per_round_s come from `api.run`'s RunResult — compile_s is
the AOT lower+compile alone and per_round_s includes the device->host
history fetch.  ``per_round_s`` is the best variant (what you'd deploy);
``per_round_full_vmap_s`` / ``per_round_microbatch_s`` break it down.
Committed results predate one machine change and two definition changes,
so compare like with like (the --smoke gate compares against the
committed file for exactly this reason).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import numpy as np

RESULTS_PATH = "results/scale_bench.json"


def _microbatch_for(n: int) -> int:
    """The sweep's microbatch schedule: ~N/4 small, capped at 200 (the
    N=800 sweet spot measured on this host; also divides the 10k mesh
    layout: 200 % 8 == 0, 1250 % 25 == 0)."""
    return min(200, max(2, n // 4))


def _scale_scenario(num_clients: int, rounds: int, *, method: str = "fedhc",
                    microbatch: int = 0, factorized: bool = False,
                    sliced: bool = False, mesh: bool = False):
    from repro.api import (CommsSpec, DataSpec, ExecSpec, FleetSpec,
                           Scenario, TrainSpec)
    return Scenario(
        method=method,
        data=DataSpec(samples_per_client=16, eval_size=256),
        fleet=FleetSpec(num_clients=num_clients,
                        num_clusters=max(4, num_clients // 100)),
        train=TrainSpec(rounds=rounds, rounds_per_global=2,
                        eval_every=rounds, local_steps=1, batch_size=16),
        comms=CommsSpec(contact_factorized=factorized,
                        contact_slices=sliced),
        exec=ExecSpec(client_microbatch=microbatch,
                      mesh_devices=0 if mesh else None),
    )


def _maybe_trace(profile_dir, tag):
    if not profile_dir:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.trace(os.path.join(profile_dir, tag))


def bench_engine(num_clients: int, rounds: int = 3,
                 profile_dir: str = None, mesh: bool = False) -> dict:
    """Full-vmap vs microbatched round timings from one shared setup
    (the synthetic dataset and client stack are built once per N —
    `api.run`'s setup_cache keys ignore exec-only knobs)."""
    from repro import api
    from repro.models.lenet import init_lenet
    import jax

    cache = {}
    mb = _microbatch_for(num_clients)
    variants = {}
    res = None
    for name, m in (("full_vmap", 0), ("microbatch", mb)):
        sc = _scale_scenario(num_clients, rounds, microbatch=m, mesh=mesh)
        with _maybe_trace(profile_dir, f"n{num_clients}_{name}"):
            r = api.run(sc, setup_cache=cache)
        variants[name] = round(r.run_s / rounds, 4)
        if res is None:
            res = r                       # setup/compile of the first run
        last = r
    assert len(cache) == 1, "setup_cache missed: exec knobs leaked in"

    ds = sc.data.dataset
    # analytic stack size: num_clients x one freshly-initialized model
    w0 = init_lenet(jax.random.PRNGKey(0), ds.channels, ds.img,
                    ds.num_classes)
    params_mb = num_clients * sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(w0)) / 1e6
    return {
        "num_clients": num_clients, "rounds": rounds,
        "setup_s": round(res.setup_s, 2),
        "compile_s": round(res.compile_s, 2),
        "per_round_s": min(variants.values()),
        "per_round_full_vmap_s": variants["full_vmap"],
        "per_round_microbatch_s": variants["microbatch"],
        "client_microbatch": mb,
        "client_stack_mb": round(params_mb, 2),
        "peak_device_mem_mb": last.peak_device_mem_mb,
    }


def bench_factorized(num_clients: int, rounds: int = 3,
                     include_stored: bool = True,
                     profile_dir: str = None) -> dict:
    """Stored-sliced vs factorized contact plans through the real engine
    (fedspace: static layout, visibility-gated).  With ``include_stored``
    the two trajectories are pinned against each other — the acceptance
    gate for recomputing routes inside the scan."""
    from repro import api

    mb = _microbatch_for(num_clients)
    out = {"num_clients": num_clients, "rounds": rounds,
           "client_microbatch": mb}
    sc_f = _scale_scenario(num_clients, rounds, method="fedspace",
                           microbatch=mb, factorized=True)
    with _maybe_trace(profile_dir, f"n{num_clients}_factorized"):
        r_f = api.run(sc_f)
    out["factorized_setup_s"] = round(r_f.setup_s, 2)
    out["factorized_per_round_s"] = round(r_f.run_s / rounds, 4)
    if include_stored:
        sc_s = _scale_scenario(num_clients, rounds, method="fedspace",
                               microbatch=mb, sliced=True)
        r_s = api.run(sc_s)
        out["stored_setup_s"] = round(r_s.setup_s, 2)
        out["stored_per_round_s"] = round(r_s.run_s / rounds, 4)
        # trajectory parity: visibility is bit-identical, so the gated
        # participation pattern — and with it the learning trajectory —
        # must match the stored plan to float tolerance
        np.testing.assert_allclose(r_f.loss, r_s.loss, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(r_f.acc, r_s.acc, atol=0.01)
        np.testing.assert_allclose(r_f.time_s, r_s.time_s, rtol=1e-4)
        out["trajectory_parity"] = True
    return out


def bench_plan_dtype(num_planes: int = 4, sats_per_plane: int = 8,
                     dt_s: float = 120.0) -> dict:
    """f32 vs bf16 route-table storage on a small constellation, plus the
    analytic (T, N, N) footprint extrapolated to the paper's N=800."""
    from repro.orbits import contact as contact_lib
    from repro.orbits.constellation import Constellation
    from repro.orbits.links import LinkParams
    import jax.numpy as jnp

    c = Constellation(num_planes=num_planes, sats_per_plane=sats_per_plane)
    f32 = contact_lib.build_contact_plan(c, LinkParams(), dt_s=dt_s)
    bf16 = contact_lib.build_contact_plan(c, LinkParams(), dt_s=dt_s,
                                          storage_dtype=jnp.bfloat16)
    a = np.asarray(f32.isl_tpb)
    b = np.asarray(bf16.isl_tpb, np.float32)
    finite = np.isfinite(a)
    rel = float(np.max(np.abs(b[finite] - a[finite])
                       / np.maximum(np.abs(a[finite]), 1e-30)))
    t800 = int(round(c.period_s / 60.0))     # dt=60 s over one period
    return {
        "num_sats": c.num_sats, "samples": int(f32.times.shape[0]),
        "isl_tpb_mb_f32": round(f32.isl_tpb.nbytes / 1e6, 3),
        "isl_tpb_mb_bf16": round(bf16.isl_tpb.nbytes / 1e6, 3),
        "max_rel_err_bf16": rel,
        "reachability_identical": bool(
            np.array_equal(np.isfinite(b), finite)),
        "n800_dt60_gb_f32": round(t800 * 800 * 800 * 4 / 1e9, 2),
        "n800_dt60_gb_bf16": round(t800 * 800 * 800 * 2 / 1e9, 2),
    }


def bench_plan_slices(num_planes: int = 4, sats_per_plane: int = 8,
                      dt_s: float = 120.0, k: int = 4) -> dict:
    """Cluster-sliced route storage ((T,N)+(T,K,N) vs (T,N,N)): measured
    on a small constellation and extrapolated to the ROADMAP target
    N=800 / K=8 / dt=10 s, where the full f32 table is ~1.5 GB and the
    sliced one must land under ~50 MB."""
    import jax.numpy as jnp
    from repro.orbits import contact as contact_lib
    from repro.orbits.constellation import Constellation
    from repro.orbits.links import LinkParams

    c = Constellation(num_planes=num_planes, sats_per_plane=sats_per_plane)
    n = c.num_sats
    assignment = jnp.asarray(np.arange(n) % k, jnp.int32)
    ps_index = jnp.asarray(np.arange(k) * (n // k), jnp.int32)
    full = contact_lib.build_contact_plan(c, LinkParams(), dt_s=dt_s)
    sliced = contact_lib.build_contact_plan(
        c, LinkParams(), dt_s=dt_s, cluster_slices=(assignment, ps_index))
    t800 = int(round(c.period_s / 10.0))     # dt=10 s over one period
    k800 = 8
    return {
        "num_sats": n, "k": k, "samples": int(full.times.shape[0]),
        "routes_mb_full": round(full.isl_tpb.nbytes / 1e6, 3),
        "routes_mb_sliced": round(
            (sliced.tpb_to_ps.nbytes + sliced.ps_rows.nbytes) / 1e6, 3),
        "n800_dt10_mb_full_f32": round(t800 * 800 * 800 * 4 / 1e6, 1),
        "n800_dt10_mb_sliced_f32": round(
            (t800 * 800 + t800 * k800 * 800) * 4 / 1e6, 1),
    }


def bench_plan_factorized(num_planes: int = 4, sats_per_plane: int = 8,
                          dt_s: float = 120.0, k: int = 4) -> dict:
    """The last rung of the storage ladder: the factorized plan stores no
    route tables at all — O(N) vs the sliced plan's O(T*(K+1)*N) — so
    plan memory stops being a function of the time grid entirely.  At
    N=10k / K=100 / dt=10s the sliced tables would be ~2.3 GB; the
    factorized plan is ~80 KB."""
    import jax
    import jax.numpy as jnp
    from repro.orbits import contact as contact_lib
    from repro.orbits.constellation import Constellation
    from repro.orbits.links import LinkParams

    c = Constellation(num_planes=num_planes, sats_per_plane=sats_per_plane)
    n = c.num_sats
    assignment = jnp.asarray(np.arange(n) % k, jnp.int32)
    ps_index = jnp.asarray(np.arange(k) * (n // k), jnp.int32)
    sliced = contact_lib.build_contact_plan(
        c, LinkParams(), dt_s=dt_s, cluster_slices=(assignment, ps_index))
    fact = contact_lib.build_factorized_plan(
        c, LinkParams(), dt_s=dt_s, cluster_slices=(assignment, ps_index))
    fact_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(fact))
    t10k = int(round(c.period_s / 10.0))
    n10k, k10k = 10_000, 100
    return {
        "num_sats": n, "k": k, "samples": int(sliced.times.shape[0]),
        "routes_mb_sliced": round(
            (sliced.tpb_to_ps.nbytes + sliced.ps_rows.nbytes) / 1e6, 3),
        "plan_kb_factorized": round(fact_bytes / 1e3, 3),
        "n10k_dt10_mb_sliced_f32": round(
            (t10k * n10k + t10k * k10k * n10k) * 4 / 1e6, 1),
        "n10k_kb_factorized": round((t10k + 2 * n10k) * 4 / 1e3, 1),
    }


def mega_smoke(num_clients: int = 10_000, rounds: int = 2) -> dict:
    """The N=10k point: fedspace on a factorized plan with microbatched
    local training, client-sharded over every local device.  Storing even
    the *sliced* route tables at this scale would be GBs — the factorized
    plan plus in-scan route recompute is what makes the config
    constructible at all."""
    import jax
    from repro import api

    ndev = len(jax.devices())
    mb = _microbatch_for(num_clients)
    sc = _scale_scenario(num_clients, rounds, method="fedspace",
                         microbatch=mb, factorized=True, mesh=ndev > 1)
    print(f"[scale] mega smoke: N={num_clients} fedspace, factorized "
          f"plan, microbatch={mb}, {ndev} device(s)")
    r = api.run(sc)
    entry = {
        "num_clients": num_clients, "rounds": rounds, "method": "fedspace",
        "devices": ndev, "client_microbatch": mb,
        "contact_factorized": True,
        "setup_s": round(r.setup_s, 2),
        "compile_s": round(r.compile_s, 2),
        "per_round_s": round(r.run_s / rounds, 4),
        "peak_device_mem_mb": r.peak_device_mem_mb,
        "final_acc": float(np.asarray(r.acc)[-1]),
    }
    print(f"[scale] mega smoke: setup {entry['setup_s']}s | compile "
          f"{entry['compile_s']}s | {entry['per_round_s']}s/round | "
          f"acc {entry['final_acc']:.3f}")
    return entry


def sharded_smoke() -> dict:
    """Tiny sharded fedhc end-to-end on a client mesh over every local
    device (the CI forced-multi-device job); asserts the client axis is
    actually sharded and the trajectory matches the single-device run."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import api
    from repro.api import (DataSpec, ExecSpec, FleetSpec, Scenario,
                           TrainSpec)
    from repro.core import engine
    from repro.launch.mesh import make_client_mesh

    ndev = len(jax.devices())
    assert ndev > 1, ("sharded smoke needs >1 device; set XLA_FLAGS="
                      "--xla_force_host_platform_device_count=8")
    mesh = make_client_mesh()
    sc = Scenario(
        method="fedhc",
        data=DataSpec(samples_per_client=32, eval_size=128),
        fleet=FleetSpec(num_clients=4 * ndev, num_clusters=3),
        train=TrainSpec(rounds=6, rounds_per_global=3, eval_every=3,
                        local_steps=1, batch_size=16))
    state0, _ = engine.setup(sc.to_flat(), mesh=mesh)
    leaf = jax.tree_util.tree_leaves(state0.params)[0]
    print(f"[scale] client mesh {dict(mesh.shape)}; param leaf "
          f"{leaf.shape} sharded as {leaf.sharding.spec} "
          f"({leaf.addressable_shards[0].data.shape[0]} clients/device)")
    jax.debug.visualize_array_sharding(leaf.reshape(leaf.shape[0], -1))
    assert P(leaf.sharding.spec[0]) == P(tuple(mesh.axis_names))
    r_sharded = api.run(sc.replace(exec=ExecSpec(mesh_devices=0)))
    r_single = api.run(sc)
    assert r_sharded.mesh_shape == {"clients": ndev}
    np.testing.assert_allclose(r_sharded.time_s, r_single.time_s,
                               rtol=1e-5)
    np.testing.assert_allclose(r_sharded.loss, r_single.loss,
                               rtol=1e-4, atol=1e-5)
    print(f"[scale] sharded-vs-single parity OK over {ndev} devices "
          f"(acc {r_sharded.acc})")
    return {"devices": ndev, "acc": r_sharded.acc.tolist()}


def _load_committed(path: str = RESULTS_PATH) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def smoke(path: str = RESULTS_PATH) -> int:
    """CI regression gate: the N=64 cell must stay within 2x of the
    committed per-round number (generous enough for shared-runner noise,
    tight enough to catch a superlinear term creeping back in)."""
    committed = _load_committed(path)
    baseline = next((p for p in committed.get("engine", [])
                     if p["num_clients"] == 64), None)
    r = bench_engine(64)
    print(f"[scale] smoke N=64: {r['per_round_s']}s/round "
          f"(full-vmap {r['per_round_full_vmap_s']}s, "
          f"microbatch({r['client_microbatch']}) "
          f"{r['per_round_microbatch_s']}s)")
    if baseline is None:
        print(f"[scale] smoke: no committed N=64 entry in {path}; "
              f"nothing to gate against")
        return 0
    limit = 2.0 * baseline["per_round_s"]
    if r["per_round_s"] > limit:
        print(f"[scale] smoke FAIL: {r['per_round_s']}s/round > 2x "
              f"committed {baseline['per_round_s']}s/round")
        return 2
    print(f"[scale] smoke OK: {r['per_round_s']}s/round <= 2x committed "
          f"{baseline['per_round_s']}s/round")
    return 0


def main(fast: bool = False, out_path: str = RESULTS_PATH,
         profile_dir: str = None) -> dict:
    sizes = (64, 256) if fast else (64, 256, 800)
    points = []
    for n in sizes:
        r = bench_engine(n, profile_dir=profile_dir)
        points.append(r)
        print(f"[scale] N={n:4d}: setup {r['setup_s']:6.2f}s | "
              f"compile {r['compile_s']:6.2f}s | "
              f"{r['per_round_full_vmap_s']*1e3:8.1f} ms/round full-vmap "
              f"-> {r['per_round_microbatch_s']*1e3:8.1f} ms/round "
              f"microbatch({r['client_microbatch']}) | "
              f"client stack {r['client_stack_mb']:7.2f} MB")
    factorized = bench_factorized(64 if fast else 256)
    print(f"[scale] factorized engine N={factorized['num_clients']}: "
          f"{factorized['stored_per_round_s']}s/round stored -> "
          f"{factorized['factorized_per_round_s']}s/round recomputed "
          f"in-scan (setup {factorized['stored_setup_s']}s -> "
          f"{factorized['factorized_setup_s']}s, trajectory parity "
          f"{factorized.get('trajectory_parity')})")
    plan = bench_plan_dtype()
    print(f"[scale] contact plan ({plan['num_sats']} sats x "
          f"{plan['samples']} samples): isl_tpb "
          f"{plan['isl_tpb_mb_f32']} MB f32 -> {plan['isl_tpb_mb_bf16']} MB "
          f"bf16 (max rel err {plan['max_rel_err_bf16']:.2e}, reachability "
          f"identical: {plan['reachability_identical']}); at N=800/dt=60s: "
          f"{plan['n800_dt60_gb_f32']} GB -> {plan['n800_dt60_gb_bf16']} GB")
    slices = bench_plan_slices()
    print(f"[scale] cluster-sliced routes ({slices['num_sats']} sats, "
          f"K={slices['k']}): {slices['routes_mb_full']} MB full -> "
          f"{slices['routes_mb_sliced']} MB sliced; at N=800/K=8/dt=10s: "
          f"{slices['n800_dt10_mb_full_f32']} MB full f32 -> "
          f"{slices['n800_dt10_mb_sliced_f32']} MB sliced "
          f"(cfg.contact_slices=True)")
    pfact = bench_plan_factorized()
    print(f"[scale] factorized plan storage: {pfact['routes_mb_sliced']} "
          f"MB sliced -> {pfact['plan_kb_factorized']} KB factorized; at "
          f"N=10k/K=100/dt=10s: {pfact['n10k_dt10_mb_sliced_f32']} MB "
          f"sliced -> {pfact['n10k_kb_factorized']} KB "
          f"(cfg.contact_factorized=True)")
    result = {"engine": points, "engine_factorized": factorized,
              "plan_dtype": plan, "plan_slices": slices,
              "plan_factorized": pfact}
    committed = _load_committed(out_path)
    if "mega_smoke" in committed:         # preserved across sweep reruns
        result["mega_smoke"] = committed["mega_smoke"]
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="drop the N=800 point")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: N=64 cell vs committed results, "
                         "fail on >2x per-round regression")
    ap.add_argument("--mega", action="store_true",
                    help="N=10k factorized+microbatched smoke; merges a "
                         "mega_smoke entry into the results file")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write jax.profiler traces for each timed run")
    ap.add_argument("--sharded-smoke", action="store_true",
                    help="tiny sharded end-to-end run (needs >1 device)")
    args = ap.parse_args()
    if args.sharded_smoke:
        sharded_smoke()
    elif args.smoke:
        sys.exit(smoke())
    elif args.mega:
        entry = mega_smoke()
        result = _load_committed(RESULTS_PATH)
        result["mega_smoke"] = entry
        os.makedirs(os.path.dirname(RESULTS_PATH), exist_ok=True)
        with open(RESULTS_PATH, "w") as f:
            json.dump(result, f, indent=2)
    else:
        main(fast=args.fast, profile_dir=args.profile)
