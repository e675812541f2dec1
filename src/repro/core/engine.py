"""Scan-compiled FL round engine: the whole multi-round simulation as ONE
compiled XLA program.

Architecture
------------
:class:`RoundState` is a pytree carrying everything a round mutates — the
client parameter stack, cluster assignment, centroids, PS indices, the loop
RNG key, cumulative simulated time/energy, and the re-cluster count.  One
round is ``round_step(state, round_index) -> (state, RoundOutput)``, and the
full run is ``jax.lax.scan(round_step, state0, jnp.arange(rounds))``:

* the orbital propagator (`orbits/constellation.py`) is pure-jnp, so
  satellite/ground-station positions are computed *inside* the scan from the
  carried simulation clock;
* the every-``m``-rounds global aggregation and the dropout-triggered
  re-cluster (Alg. 1 lines 14-18, including ``kmeans`` and the §III-C MAML
  hand-off) are ``jax.lax.cond`` branches, so no per-round host syncs exist
  anywhere — a 150-round run does exactly one device→host transfer, for the
  stacked :class:`RoundOutput` history at the end;
* method behavior comes from the :mod:`repro.core.strategies` registry:
  clustering init, weighting rule, re-cluster policy, inheritance rule,
  cost model and connectivity are composable `Strategy` fields, not string
  branches;
* time-varying connectivity (``Strategy.connectivity != "always"``) rides
  on a precomputed contact plan (`orbits/contact.py`): ``setup`` samples
  ground-station visibility and all-pairs bounded-hop ISL route costs
  over one orbital period as device arrays, and the scan *gathers* from
  them by the carried simulation clock — participation is gated by ISL
  reachability to the cluster PS, uploads cost hop-by-hop route time, and
  a due stage-2 aggregation that finds no contact window sets the carried
  ``pending_global`` flag and retries every subsequent round until a
  window opens (FedSpace-style deferral), all without host syncs;
* **paper-scale SPMD** (``mesh=`` on ``setup``/``simulate``/``run``): the
  whole round scan runs as one mesh-aware program.  ``setup`` places the
  client-stacked params with ``NamedSharding`` from
  `sharding/rules.tree_param_specs(client_stacked=True)` and the
  per-client ``SimData`` arrays (``client_idx``/``data_sizes``/``freqs``)
  on the client axes, so ``_local_train``'s vmap over clients
  parallelizes across devices; the aggregation goes through the merged
  `core/aggregation_spmd.hierarchical_round_sharded` formulation (the
  one-hot segment-matmul oracle math + sharding pins, so dynamic
  re-clustering stays a data change — no recompile, no replication); the
  contact-plan rows are sharded over the client axes too, so the
  per-round gathers never force a replicated (N, N) copy.  With
  ``mesh=None`` (the default) no constraint ops are emitted and the
  trajectory stays bit-compatible with the pre-mesh engine
  (``tests/golden/engine_always.json``).  Client counts must divide the
  client-axis size (``launch/mesh.validate_client_sharding`` raises
  otherwise).

One-time setup (synthetic data, model init, initial clustering + PS
selection) runs eagerly on the host, exactly like the legacy loop: it is
O(1) per experiment, and keeping it out of the compiled program makes the
engine trajectory bit-compatible with ``run_fl_legacy`` at round 0 (XLA
fuses multiply-adds inside large jitted programs, which can flip argmin
tie-breaks in the symmetric t=0 constellation geometry).

Entry points
------------
``run(cfg)`` mirrors the legacy ``run_fl`` history dict (the compatibility
wrapper in `core/fedhc.py` routes ``run_fl`` here).  ``simulate(cfg, seed)``
returns the raw per-round arrays on device.  ``run_many_seeds(cfg, seeds)``
stacks per-seed setups and ``vmap``s the round scan, so a multi-seed sweep
is a single compiled call (note: under ``vmap``, ``lax.cond`` lowers to
``select``, so per-seed branches both execute; the win is batching across
the sweep, not branch skipping).  ``run``/``simulate``/``setup`` accept
``mesh=``/``client_axes=`` for the sharded paper-scale path, and
``cfg.use_pallas_kernels`` routes the scan hot path (k-means assignment,
stage-1 weighted aggregation) through the Pallas kernels.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import aggregation_spmd as agg_spmd
from repro.core import clustering as cl
from repro.core import strategies as strat_lib
from repro.core.fedhc import (FLRunConfig, _adapt_members, _local_train,
                              _meta_update_clusters)
from repro.data.synthetic import (client_batches, dirichlet_partition,
                                  gather_samples, make_split, sample_rows)
from repro.launch import mesh as mesh_lib
from repro.models.lenet import init_lenet, lenet_accuracy, lenet_loss
from repro.obs.telemetry import Telemetry
from repro.obs.trace import COUNTERS, phase_scope
from repro.orbits import contact as contact_lib
from repro.orbits import cost as cost_lib
from repro.orbits import topology as topo_lib
from repro.orbits.constellation import Constellation, ground_station_position
from repro.orbits.links import LinkParams
from repro.sharding import rules as shard_rules


class RoundState(NamedTuple):
    """Everything one FL round mutates, as a scan carry."""
    params: Any                # (C, ...) client stack, or the server model
    assignment: jnp.ndarray    # (C,) int32 cluster id per satellite
    centroids: jnp.ndarray    # (K, 3) position-space centroids
    ps_index: jnp.ndarray      # (K,) int32 satellite chosen as cluster PS
    rng: jax.Array             # loop key; per-round keys fold in the index
    t_sim: jnp.ndarray         # () f32 cumulative simulated time (s)
    e_sim: jnp.ndarray         # () f32 cumulative energy (J)
    reclusters: jnp.ndarray    # () int32 re-cluster events so far
    pending_global: jnp.ndarray  # () bool: a due stage-2 aggregation is
    #                              waiting for a contact window (always
    #                              False for connectivity="always")


class RoundOutput(NamedTuple):
    """Per-round scan output; stacked over rounds = the full history."""
    acc: jnp.ndarray           # test accuracy (NaN on non-eval rounds)
    loss: jnp.ndarray          # mean training loss this round
    time_s: jnp.ndarray        # cumulative time after this round
    energy_j: jnp.ndarray      # cumulative energy after this round
    reclustered: jnp.ndarray   # int32 0/1: re-cluster fired this round
    evaluated: jnp.ndarray     # bool: acc is valid this round
    did_global: jnp.ndarray    # int32 0/1: stage-2 aggregation fired


class SimData(NamedTuple):
    """Per-experiment arrays the rounds read but never mutate."""
    images: jnp.ndarray        # (N, H, W, ch) training pool; the scans
    #                            gather its rows (`data/synthetic.py`)
    labels: jnp.ndarray        # (N,)
    test_x: jnp.ndarray
    test_y: jnp.ndarray
    client_idx: jnp.ndarray    # (C, samples_per_client) per-client indices
    data_sizes: jnp.ndarray    # (C,) f32
    freqs: jnp.ndarray         # (C,) heterogeneous CPU frequencies
    r_kmeans: jax.Array        # key the re-cluster kmeans folds the round into
    plan: Optional[contact_lib.ContactPlan]  # contact plan (None when the
    #                                          strategy is always-up)


def _ps_of(positions, centroids, assignment, k):
    """PS selection: per cluster, the member nearest its centroid."""
    d = cl.pairwise_sq_dist(positions, centroids)
    same = jax.nn.one_hot(assignment, k, dtype=bool).T
    return jnp.argmin(jnp.where(same, d.T, jnp.inf), axis=1).astype(jnp.int32)


def _constellation_for(num_clients: int) -> Constellation:
    planes = int(math.sqrt(num_clients))
    while num_clients % planes:
        planes -= 1
    return Constellation(num_planes=planes,
                         sats_per_plane=num_clients // planes)


def _plan_for(cfg: FLRunConfig, strategy: strat_lib.Strategy,
              cluster_slices=None):
    """Build the contact plan a config needs — None for always-up
    strategies.  Without ``cluster_slices`` the plan is seed-independent
    (shareable across a sweep); passing ``(assignment, ps_index)`` builds
    the cluster-sliced storage form instead (`orbits/contact.py`), which
    is seed-*dependent* and only valid for a static cluster layout."""
    if not strategy.visibility_gated:
        return None
    if cluster_slices is not None and strategy.reclusters:
        raise ValueError("contact_slices/contact_factorized require a "
                         "static cluster layout (recluster='never'): the "
                         "plan only covers the build-time PS set")
    if cfg.contact_factorized:
        if strategy.is_async:
            raise ValueError(
                "contact_factorized=True is sync-engine-only: the async "
                "engine looks routes up at per-client clocks, which would "
                "recompute the relaxation once per client (store the plan "
                "instead: contact_slices=True)")
        if cfg.contact_slices:
            raise ValueError("contact_slices and contact_factorized are "
                             "mutually exclusive storage layouts")
        return contact_lib.build_factorized_plan(
            _constellation_for(cfg.num_clients), LinkParams(),
            dt_s=cfg.contact_dt_s,
            min_elevation_deg=cfg.gs_min_elevation_deg,
            max_range_km=cfg.isl_max_range_km, max_hops=cfg.isl_max_hops,
            cluster_slices=cluster_slices)
    return contact_lib.build_contact_plan(
        _constellation_for(cfg.num_clients), LinkParams(),
        dt_s=cfg.contact_dt_s,
        min_elevation_deg=cfg.gs_min_elevation_deg,
        max_range_km=cfg.isl_max_range_km, max_hops=cfg.isl_max_hops,
        storage_dtype=jnp.dtype(cfg.contact_dtype),
        cluster_slices=cluster_slices)


def _resolve_client_axes(mesh, client_axes):
    """Placement: which mesh axes carry the client dim.  ``None`` means
    the whole mesh (the FL model is tiny, so every axis is a client
    axis unless the caller says otherwise)."""
    if mesh is None:
        return None
    if client_axes is None:
        return tuple(mesh.axis_names)
    if isinstance(client_axes, str):
        return (client_axes,)
    return tuple(client_axes)


def _data_shardings(cfg: FLRunConfig, strategy: strat_lib.Strategy,
                    data: SimData, mesh, caxes) -> SimData:
    """Sharding pytree for :class:`SimData`: per-client arrays shard their
    leading dim over the client axes, contact-plan *rows* shard over the
    client axes too (so lookup gathers never pull a replicated (N, N)
    slice), everything else is replicated.  Shared with the async engine
    (`core/async_engine.py`), whose SimData layout is identical."""
    repl = NamedSharding(mesh, P())
    if strategy.shardable:
        cvec = NamedSharding(
            mesh, shard_rules.client_spec(mesh, caxes, cfg.num_clients))
    else:
        cvec = repl
    plan_sh = None
    if data.plan is not None:
        row = (shard_rules.client_spec(mesh, caxes, cfg.num_clients)
               if strategy.shardable else P())
        row_sh = NamedSharding(mesh, P(None, *row))
        if isinstance(data.plan, contact_lib.FactorizedContactPlan):
            # nothing big to shard: the plan is O(N) generator inputs
            # (time grid + cluster layout); the recomputed per-round
            # slices get their layout from GSPMD propagation
            plan_sh = jax.tree_util.tree_map(lambda _: repl, data.plan)
        elif isinstance(data.plan, contact_lib.ClusterContactPlan):
            plan_sh = contact_lib.ClusterContactPlan(
                times=repl, gs_visible=row_sh, gs_dist_km=row_sh,
                tpb_to_ps=row_sh,
                ps_rows=NamedSharding(mesh, P(None, None, *row)))
        else:
            plan_sh = contact_lib.ContactPlan(
                times=repl, gs_visible=row_sh, gs_dist_km=row_sh,
                isl_tpb=row_sh)
    return SimData(images=repl, labels=repl, test_x=repl, test_y=repl,
                   client_idx=cvec, data_sizes=cvec, freqs=cvec,
                   r_kmeans=repl, plan=plan_sh)


def _place(cfg: FLRunConfig, strategy: strat_lib.Strategy,
           state0: RoundState, data: SimData, mesh,
           caxes) -> tuple[RoundState, SimData]:
    """Lay the experiment out on a mesh: the client-stacked params and the
    per-client SimData arrays shard their leading dim over the client
    axes; everything else (data pool, clustering state, contact-plan
    sample axis) is replicated."""
    repl = NamedSharding(mesh, P())
    if strategy.shardable:
        mesh_lib.validate_client_sharding(mesh, caxes, cfg.num_clients)
        pspecs = shard_rules.tree_param_specs(
            state0.params, mesh, client_axes=caxes, client_stacked=True)
        param_sh = shard_rules.tree_shardings(pspecs, mesh)
    else:
        param_sh = jax.tree_util.tree_map(lambda _: repl, state0.params)

    state_sh = jax.tree_util.tree_map(lambda _: repl, state0)
    state_sh = state_sh._replace(params=param_sh)
    data_sh = _data_shardings(cfg, strategy, data, mesh, caxes)
    return jax.device_put(state0, state_sh), jax.device_put(data, data_sh)


def _broadcast_client_stack(w0, num_clients: int, mesh, caxes):
    """Per-host sharded build of the (C, ...) client parameter stack:
    ``broadcast_global`` without ever materializing the full stack on any
    host.  Each leaf is handed to ``jax.make_array_from_process_local_data``
    as a zero-copy ``np.broadcast_to`` view (stride-0 leading dim), so the
    host-side footprint stays O(model) while the device shards land
    directly under their NamedSharding — at N=10k the host never holds
    the ~1.7 GB stack the host-0 broadcast path would allocate.  In a
    multi-process mesh each process feeds only its addressable portion."""
    mesh_lib.validate_client_sharding(mesh, caxes, num_clients)
    stack_shapes = jax.eval_shape(
        lambda w: agg.broadcast_global(w, num_clients), w0)
    pspecs = shard_rules.tree_param_specs(
        stack_shapes, mesh, client_axes=caxes, client_stacked=True)
    shardings = shard_rules.tree_shardings(pspecs, mesh)

    local_rows = mesh_lib.process_local_client_rows(num_clients)

    def build(leaf, sharding):
        global_shape = (num_clients,) + leaf.shape
        view = np.broadcast_to(np.asarray(leaf)[None],
                               (local_rows,) + leaf.shape)
        return jax.make_array_from_process_local_data(
            sharding, view, global_shape)

    return jax.tree_util.tree_map(build, w0, shardings)


def setup(cfg: FLRunConfig, seed: Optional[int] = None,
          contact_plan: Optional[contact_lib.ContactPlan] = None,
          mesh=None, client_axes=None) -> tuple[RoundState, SimData]:
    """One-time experiment setup (host side, same RNG stream layout as the
    legacy loop): synthetic data, model init, strategy-pluggable initial
    clustering, PS selection.  ``contact_plan`` lets multi-seed sweeps
    share one prebuilt plan (it is seed-independent) instead of paying
    the O(T * N^3) build per seed.

    ``mesh`` (with optional ``client_axes``, default: every mesh axis)
    lays the experiment out for sharded execution — see :func:`_place`.
    The RNG streams and values are identical either way; only the device
    placement differs."""
    strategy = strat_lib.get(cfg.method)
    ds = cfg.dataset
    k = 1 if strategy.centralized else cfg.num_clusters
    n_total = cfg.num_clients * cfg.samples_per_client

    rng = jax.random.PRNGKey(cfg.seed if seed is None else seed)
    r_data, r_part, r_model, r_freq, r_kmeans, r_loop = \
        jax.random.split(rng, 6)

    (images, labels), (test_x, test_y) = make_split(
        r_data, ds, n_total, cfg.eval_size)
    client_idx = dirichlet_partition(r_part, labels, cfg.num_clients,
                                     cfg.dirichlet_alpha,
                                     cfg.samples_per_client,
                                     num_classes=ds.num_classes)
    data_sizes = jnp.full((cfg.num_clients,), cfg.samples_per_client,
                          jnp.float32)

    w0 = init_lenet(r_model, ds.channels, ds.img, ds.num_classes)
    freqs = cost_lib.sample_freqs(r_freq, cfg.num_clients,
                                  cost_lib.ComputeParams())

    pos0 = _constellation_for(cfg.num_clients).positions(0.0)
    hists = jax.vmap(lambda idx: jnp.bincount(
        labels[idx], length=ds.num_classes))(client_idx)
    hists = (hists / cfg.samples_per_client).astype(jnp.float32)
    init_fn = strat_lib.CLUSTER_INITS[strategy.cluster_init]
    assignment0, centroids0 = init_fn(r_kmeans, pos0, hists, k)
    ps_index0 = _ps_of(pos0, centroids0, assignment0, k)

    caxes = _resolve_client_axes(mesh, client_axes)
    if strategy.centralized:
        params0 = w0
    elif mesh is not None and strategy.shardable:
        # per-host sharded build: no host materializes the full stack
        params0 = _broadcast_client_stack(w0, cfg.num_clients, mesh, caxes)
    else:
        params0 = agg.broadcast_global(w0, cfg.num_clients)
    state0 = RoundState(params0, assignment0.astype(jnp.int32), centroids0,
                        ps_index0, r_loop, jnp.float32(0.0),
                        jnp.float32(0.0), jnp.int32(0), jnp.bool_(False))
    # one-time eager build; the compiled rounds only gather from it
    # (the factorized plan instead re-derives its slices in-scan)
    slices = ((assignment0.astype(jnp.int32), ps_index0)
              if (cfg.contact_slices or cfg.contact_factorized) else None)
    plan = (contact_plan if contact_plan is not None
            else _plan_for(cfg, strategy, cluster_slices=slices))
    data = SimData(images, labels, test_x, test_y, client_idx, data_sizes,
                   freqs, r_kmeans, plan)
    if mesh is not None:
        state0, data = _place(cfg, strategy, state0, data, mesh, caxes)
    return state0, data


def _scan_fn(cfg: FLRunConfig, mesh=None, client_axes=None):
    """Build (and cache) the jitted ``(state0, data) -> (state, outputs)``
    round scan for a config.  ``FLRunConfig`` is frozen, hence hashable;
    ``mesh`` (hashable too) selects the sharded program variant — with
    ``mesh=None`` no sharding constraint ops are emitted, keeping the
    single-device program identical to the pre-mesh engine.  Thin
    canonicalizing wrapper so ``_scan_fn(cfg)`` and
    ``_scan_fn(cfg, None, None)`` share one cache entry (one compile)."""
    return _scan_fn_cached(cfg, mesh, _resolve_client_axes(mesh,
                                                           client_axes))


@functools.lru_cache(maxsize=32)
def _scan_fn_cached(cfg: FLRunConfig, mesh, client_axes):
    strategy = strat_lib.get(cfg.method)
    if strategy.is_async:
        raise ValueError(
            f"{cfg.method!r} uses async-buffered aggregation: its scan "
            f"lives in repro.core.async_engine (engine.run/simulate "
            f"route there automatically)")
    ds = cfg.dataset
    k = 1 if strategy.centralized else cfg.num_clusters
    n_total = cfg.num_clients * cfg.samples_per_client
    constellation = _constellation_for(cfg.num_clients)
    lp, cp = LinkParams(), cost_lib.ComputeParams()
    sample_bits = ds.img ** 2 * ds.channels * 32.0
    img_shape = (ds.img, ds.img, ds.channels)
    use_pallas = cfg.use_pallas_kernels
    telem_on = cfg.telemetry    # emit repro.obs Telemetry as extra scan
    #                             outputs; the fed_step/* phase scopes
    #                             are always on and name ops only, so
    #                             off compiles the same instructions as
    #                             an engine without any obs code
    if use_pallas:
        # lazy: the default path must not require jax.experimental.pallas
        from repro.kernels import ops as kernel_ops

    caxes = _resolve_client_axes(mesh, client_axes)
    sharded = mesh is not None and strategy.shardable
    if sharded:
        mesh_lib.validate_client_sharding(mesh, caxes, cfg.num_clients)
        cvec_sharding = NamedSharding(
            mesh, shard_rules.client_spec(mesh, caxes, cfg.num_clients))

        def shard_clients(x):
            """Pin a (C, ...) per-client array's leading dim to the
            client mesh axes."""
            return jax.lax.with_sharding_constraint(x, cvec_sharding)
    else:
        def shard_clients(x):
            return x

    # per-client work (local SGD, MAML re-cluster) runs over the same
    # client blocks: `client_microbatch` bounds its activation memory
    blocking = dict(microbatch=cfg.client_microbatch,
                    client_shards=(shard_rules.axis_size(mesh, caxes)
                                   if sharded else 1))

    def run_scan(state0: RoundState, data: SimData):
        with phase_scope("fed_step/batches"):
            rows = sample_rows(data.images)     # once a call, not a round
        model_bits = sum(
            x.size for x in jax.tree_util.tree_leaves(state0.params))
        if not strategy.centralized:
            model_bits //= cfg.num_clients
        model_bits *= 32.0

        if sharded:
            pspecs = shard_rules.tree_param_specs(
                state0.params, mesh, client_axes=caxes, client_stacked=True)
            param_shardings = shard_rules.tree_shardings(pspecs, mesh)
        else:
            param_shardings = None

        def shard_params(tree):
            if param_shardings is None:
                return tree
            return jax.lax.with_sharding_constraint(tree, param_shardings)

        def finish(state, rnd, params, assignment, centroids, ps_index,
                   reclustered, loss_val, t_r, e_r, pending_next,
                   did_global, global_model_fn, telem=None):
            with phase_scope("fed_step/orbit"):
                t_new = state.t_sim + t_r + cfg.round_minutes * 60.0
                e_new = state.e_sim + e_r
            with phase_scope("fed_step/eval"):
                evaluated = (((rnd + 1) % cfg.eval_every == 0)
                             | (rnd == cfg.rounds - 1))
                acc = jax.lax.cond(
                    evaluated,
                    lambda _: lenet_accuracy(global_model_fn(), data.test_x,
                                             data.test_y),
                    lambda _: jnp.float32(jnp.nan), None)
            with phase_scope("fed_step/recluster"):
                reclusters = state.reclusters + reclustered
            new_state = RoundState(params, assignment, centroids, ps_index,
                                   state.rng, t_new, e_new, reclusters,
                                   pending_next)
            out = RoundOutput(acc, loss_val, t_new, e_new, reclustered,
                              evaluated, did_global)
            if telem is not None:
                # telemetry rides as an extra scan output: same transfer,
                # same carry — the trajectory cannot change
                return new_state, (out, telem)
            return new_state, out

        # ---- one federated round (fedhc / fedhc-nomaml / h-base / fedce
        # ----  / fedspace / isl-onboard) ----------------------------------
        def fed_step(state, rnd):
            # every op of the round sits under one fed_step/* scope:
            # batches, orbit (positions, drift, contact plan, costs),
            # local_train, aggregate, recluster, eval (and telemetry)
            with phase_scope("fed_step/batches"):
                r_rnd = jax.random.fold_in(state.rng, rnd)
            with phase_scope("fed_step/orbit"):
                positions = constellation.positions(state.t_sim)
                cadence_due = (rnd + 1) % cfg.rounds_per_global == 0

            with phase_scope("fed_step/batches"):
                imgs, labs = client_batches(rows, data.labels,
                                            data.client_idx, r_rnd,
                                            cfg.batch_size, img_shape)
                imgs, labs = shard_clients(imgs), shard_clients(labs)

            with phase_scope("fed_step/orbit"):
                # geometry drift: a satellite whose nearest centroid
                # changed has "left" its cluster (Alg. 1) — drives the
                # dropout rate.
                if use_pallas:
                    nearest, _ = kernel_ops.kmeans_assign(positions,
                                                          state.centroids)
                else:
                    nearest = cl.assign(positions, state.centroids)
                in_region = nearest == state.assignment

                if strategy.visibility_gated:
                    # contact-plan gathers: who can route to whom *right
                    # now* (a cluster-sliced plan stores member->PS and
                    # PS-row routes directly; a factorized plan recomputes
                    # the same tuple from geometry; a full plan derives
                    # the slices)
                    if isinstance(data.plan,
                                  (contact_lib.ClusterContactPlan,
                                   contact_lib.FactorizedContactPlan)):
                        gs_vis, gs_dist, tpb_to_ps, ps_rows = \
                            contact_lib.lookup_sliced(data.plan, state.t_sim)
                    else:
                        gs_vis, gs_dist, tpb = contact_lib.lookup(
                            data.plan, state.t_sim)
                        ps_of_member = state.ps_index[state.assignment]
                        tpb_to_ps = tpb[jnp.arange(cfg.num_clients),
                                        ps_of_member]                 # (C,)
                        ps_rows = tpb[state.ps_index]                 # (K,C)
                    # a member participates iff a bounded-hop ISL route to
                    # its PS exists (the PS itself always does: tpb
                    # diagonal is 0)
                    participating = jnp.isfinite(tpb_to_ps)
                    ps_tpb = ps_rows[:, state.ps_index]               # (K,K)
                    if strategy.isl_global:
                        # on-board consensus: needs every PS pair connected
                        window = jnp.all(jnp.isfinite(ps_tpb))
                        t_g, e_g = cost_lib.isl_consensus_costs(
                            ps_tpb, model_bits=model_bits, lp=lp)
                    else:
                        # relay gateway: the GS-visible satellite
                        # minimizing the worst PS route (inf when none is
                        # visible)
                        worst = jnp.max(ps_rows, axis=0)              # (C,)
                        score = jnp.where(gs_vis, worst, jnp.inf)
                        gateway = jnp.argmin(score).astype(jnp.int32)
                        window = jnp.isfinite(score[gateway])
                        t_g, e_g = cost_lib.routed_ground_round_costs(
                            ps_rows[:, gateway], gs_dist[gateway],
                            model_bits=model_bits, lp=lp)
                    due = cadence_due | state.pending_global
                    do_global = due & window
                    pending_next = due & ~window
                else:
                    gs = ground_station_position(t_s=state.t_sim)
                    participating = jnp.ones_like(in_region)
                    do_global = cadence_due
                    pending_next = state.pending_global    # stays False

            with phase_scope("fed_step/local_train"):
                params, losses = _local_train(
                    state.params, imgs, labs, lr=cfg.lr,
                    steps=cfg.local_steps, **blocking)
                params = shard_params(params)
                losses = shard_clients(losses)
            # the merged aggregation formulation: oracle math + sharding
            # pins, traced do_global, dynamic assignment (no recompile)
            with phase_scope("fed_step/aggregate"):
                params = agg_spmd.hierarchical_round_sharded(
                    params, losses, data.data_sizes, state.assignment, k,
                    do_global, loss_weighted=strategy.loss_weighted,
                    participating=participating, use_pallas=use_pallas,
                    shardings=param_shardings)
            with phase_scope("fed_step/local_train"):
                loss_val = jnp.mean(losses)

            with phase_scope("fed_step/orbit"):
                if strategy.visibility_gated:
                    t_r, e_r = cost_lib.routed_cluster_round_costs(
                        tpb_to_ps, participating, data.data_sizes,
                        data.freqs, model_bits=model_bits, lp=lp, cp=cp)
                else:
                    ps_positions = positions[state.ps_index][
                        state.assignment]
                    t_r, e_r = cost_lib.cluster_round_costs(
                        positions, ps_positions, state.assignment,
                        participating, data.data_sizes, data.freqs,
                        model_bits=model_bits, lp=lp, cp=cp)
                    t_g, e_g = cost_lib.ground_round_costs(
                        positions[state.ps_index], gs,
                        model_bits=model_bits, lp=lp)
                t_r = t_r + jnp.where(do_global, t_g, 0.0)
                e_r = e_r + jnp.where(do_global, e_g, 0.0)

            assignment, centroids, ps_index = (state.assignment,
                                               state.centroids,
                                               state.ps_index)
            reclustered = jnp.int32(0)
            if strategy.reclusters:
                # ---- re-cluster check (Alg. 1 lines 14-18) ---------------
                def do_recluster(operand):
                    params, assignment, centroids, ps_index = operand
                    with phase_scope("kmeans"):
                        res = cl.kmeans(
                            positions, k,
                            jax.random.fold_in(data.r_kmeans, rnd))
                    new_assignment = res.assignment
                    cluster_models = agg.cluster_aggregate(
                        params,
                        agg.loss_weights(losses, new_assignment, k),
                        new_assignment, k, use_pallas=use_pallas)
                    if strategy.maml:
                        with phase_scope("maml"):
                            cluster_models = _meta_update_clusters(
                                cluster_models, new_assignment, imgs, labs,
                                k=k, alpha=cfg.maml_alpha,
                                beta=cfg.maml_beta, **blocking)
                    inherited = agg.broadcast_clusters(cluster_models,
                                                       new_assignment)
                    if strategy.maml:
                        # joining members take MAML inner steps on their own
                        # data from the meta-updated cluster model (§III-C)
                        with phase_scope("adapt"):
                            inherited = _adapt_members(
                                inherited, imgs, labs,
                                alpha=cfg.maml_alpha, **blocking)
                    changed = new_assignment != assignment
                    params = jax.tree_util.tree_map(
                        lambda inh, old: jnp.where(
                            changed.reshape((-1,) + (1,) * (inh.ndim - 1)),
                            inh, old), inherited, params)
                    return (params, new_assignment, res.centroids,
                            res.ps_index, jnp.int32(1))

                def no_recluster(operand):
                    return operand + (jnp.int32(0),)

                with phase_scope("fed_step/recluster"):
                    d_r = cl.dropout_rate(in_region, state.assignment, k)
                    fire = do_global & (jnp.max(d_r) > cfg.dropout_threshold)
                    (params, assignment, centroids, ps_index,
                     reclustered) = jax.lax.cond(
                        fire, do_recluster, no_recluster,
                        (params, assignment, centroids, ps_index))
                    params = shard_params(params)

            telem = None
            if telem_on:
                # outputs only: every value below is derived from round
                # intermediates and feeds nothing back into the carry
                with phase_scope("fed_step/telemetry"):
                    part_f = participating.astype(jnp.float32)
                    n_part = jnp.sum(part_f).astype(jnp.int32)
                    members = jnp.sum(jax.nn.one_hot(
                        assignment, k, dtype=jnp.float32), axis=0)
                    e_cmp = jnp.sum(part_f * cost_lib.compute_energy_j(
                        data.data_sizes, data.freqs, cp))
                    bits1 = 2.0 * model_bits * n_part.astype(jnp.float32)
                    per_global = (model_bits * k * (k - 1)
                                  if strategy.isl_global
                                  else 2.0 * model_bits * k)
                    bits2 = jnp.where(do_global, jnp.float32(per_global),
                                      0.0)
                    if strategy.visibility_gated:
                        # member->PS hop counts on this round's ISL graph
                        # (row-sliced bounded relaxation, K sources)
                        adj = topo_lib.isl_adjacency(
                            positions, cfg.isl_max_range_km)
                        hrows = topo_lib.hop_rows(adj, state.ps_index,
                                                  cfg.isl_max_hops)
                        hops = hrows[state.assignment,
                                     jnp.arange(cfg.num_clients)]
                        routed = participating & jnp.isfinite(hops)
                        n_routed = jnp.sum(routed.astype(jnp.float32))
                        hops_mean = (jnp.sum(jnp.where(routed, hops, 0.0))
                                     / jnp.maximum(n_routed, 1.0))
                        hops_max = jnp.max(jnp.where(routed, hops, 0.0))
                    else:
                        hops_mean = hops_max = jnp.float32(0.0)
                    z = jnp.float32(0.0)
                    telem = Telemetry(
                        cohort_size=jnp.int32(cfg.num_clients),
                        accepted=n_part, cluster_fill=members,
                        stale_min=z, stale_mean=z, stale_max=z,
                        flushes=jnp.int32(k),
                        did_global=do_global.astype(jnp.int32),
                        reclustered=reclustered,
                        bits_stage1=bits1, bits_stage2=bits2,
                        t_round_s=t_r + cfg.round_minutes * 60.0,
                        e_compute_j=e_cmp, e_comm_j=e_r - e_cmp,
                        hops_mean=hops_mean, hops_max=hops_max)

            with phase_scope("fed_step/orbit"):
                did_global = do_global.astype(jnp.int32)
            return finish(
                state, rnd, params, assignment, centroids, ps_index,
                reclustered, loss_val, t_r, e_r, pending_next, did_global,
                lambda: jax.tree_util.tree_map(
                    lambda x: jnp.mean(x.astype(jnp.float32), 0), params),
                telem)

        # ---- one centralized round (c-fedavg) ----------------------------
        def central_step(state, rnd):
            with phase_scope("fed_step/batches"):
                r_rnd = jax.random.fold_in(state.rng, rnd)
            with phase_scope("fed_step/orbit"):
                positions = constellation.positions(state.t_sim)
            model = state.params

            def sgd(model, s):
                b = jax.random.fold_in(r_rnd, s)
                picks = jax.random.randint(b, (cfg.batch_size,), 0, n_total)
                l, g = jax.value_and_grad(lenet_loss)(
                    model, (gather_samples(rows, picks, img_shape),
                            data.labels[picks]))
                model = jax.tree_util.tree_map(
                    lambda a, gg: a - cfg.lr * gg, model, g)
                return model, l

            with phase_scope("fed_step/local_train"):
                if cfg.local_steps > 0:
                    model, ls = jax.lax.scan(sgd, model,
                                             jnp.arange(cfg.local_steps))
                    loss_val = ls[-1]
                else:
                    # no training this round: report the current model's
                    # loss
                    picks = jax.random.randint(
                        jax.random.fold_in(r_rnd, 0), (cfg.batch_size,), 0,
                        n_total)
                    loss_val = lenet_loss(
                        model, (gather_samples(rows, picks, img_shape),
                                data.labels[picks]))

            with phase_scope("fed_step/orbit"):
                participating = jnp.ones((cfg.num_clients,), bool)
                server_pos = positions[state.ps_index[0]]
                t_r, e_r = cost_lib.cfedavg_round_costs(
                    positions, server_pos, participating, data.data_sizes,
                    data.freqs, sample_bits=sample_bits,
                    server_freq_hz=cp.max_freq_hz, lp=lp, cp=cp)

            telem = None
            if telem_on:
                # raw-data uplink + central training: stage-1 traffic is
                # the sample upload, compute energy is the server's
                with phase_scope("fed_step/telemetry"):
                    t_train = (jnp.sum(data.data_sizes)
                               * cp.cycles_per_sample / cp.max_freq_hz)
                    e_train = cp.eps0 * cp.max_freq_hz * t_train
                    z = jnp.float32(0.0)
                    telem = Telemetry(
                        cohort_size=jnp.int32(cfg.num_clients),
                        accepted=jnp.int32(cfg.num_clients),
                        cluster_fill=jnp.full((k,), float(cfg.num_clients),
                                              jnp.float32),
                        stale_min=z, stale_mean=z, stale_max=z,
                        flushes=jnp.int32(0), did_global=jnp.int32(0),
                        reclustered=jnp.int32(0),
                        bits_stage1=(jnp.sum(data.data_sizes)
                                     * sample_bits).astype(jnp.float32),
                        bits_stage2=z,
                        t_round_s=t_r + cfg.round_minutes * 60.0,
                        e_compute_j=e_train, e_comm_j=e_r - e_train,
                        hops_mean=z, hops_max=z)

            return finish(state, rnd, model, state.assignment,
                          state.centroids, state.ps_index, jnp.int32(0),
                          loss_val, t_r, e_r, state.pending_global,
                          jnp.int32(0), lambda: model, telem)

        step = central_step if strategy.centralized else fed_step
        return jax.lax.scan(step, state0, jnp.arange(cfg.rounds))

    return jax.jit(run_scan)


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------


def simulate(cfg: FLRunConfig, seed: Optional[int] = None, *,
             mesh=None, client_axes=None):
    """One compiled run -> (final RoundState, stacked RoundOutput) on
    device.  No host syncs happen inside the round loop.  ``mesh`` runs
    the sharded program variant (client axis over the mesh).  Async
    strategies route to `core/async_engine.simulate` (returning its
    ``(AsyncState, AsyncOutput)`` types instead)."""
    if strat_lib.get(cfg.method).is_async:
        from repro.core import async_engine   # late: it imports this module
        return async_engine.simulate(cfg, seed, mesh=mesh,
                                     client_axes=client_axes)
    client_axes = _resolve_client_axes(mesh, client_axes)  # hashable key
    state0, data = setup(cfg, seed, mesh=mesh, client_axes=client_axes)
    return _scan_fn(cfg, mesh, client_axes)(state0, data)


def split_outputs(outs):
    """``(outputs, telemetry_or_None)``: a telemetry-on scan stacks a
    ``(RoundOutput, Telemetry)`` pair per round — a plain tuple, while
    the bare outputs are NamedTuples (``_fields``).  Shared with the
    async engine (whose pair is ``(AsyncOutput, Telemetry)``)."""
    if isinstance(outs, tuple) and not hasattr(outs, "_fields"):
        return outs
    return outs, None


def eval_point_lists(outs):
    """Fetch a stacked output and extract the per-eval-point lists common
    to both engines (``evaluated``-masked round/acc/loss/time/energy).
    Returns ``(fetched_outs, partial_history)``; the callers add their
    own totals.  One extraction, shared by ``run``, the async engine and
    `repro.api.run` — so every entrypoint is bit-identical by
    construction."""
    outs = jax.device_get(outs)
    idx = np.nonzero(np.asarray(outs.evaluated))[0]
    return outs, {
        "round": [int(i) + 1 for i in idx],
        "acc": [float(outs.acc[i]) for i in idx],
        "loss": [float(outs.loss[i]) for i in idx],
        "time_s": [float(outs.time_s[i]) for i in idx],
        "energy_j": [float(outs.energy_j[i]) for i in idx],
    }


def history_from_outputs(outs: RoundOutput) -> Dict[str, list]:
    """Host-side history dict from a stacked :class:`RoundOutput` (a
    telemetry-carrying ``(RoundOutput, Telemetry)`` pair is split and the
    telemetry dropped — `repro.api.run` extracts it separately)."""
    outs, _ = split_outputs(outs)
    outs, history = eval_point_lists(outs)
    history["reclusters"] = int(np.sum(outs.reclustered))
    history["global_rounds"] = int(np.sum(outs.did_global))
    return history


def run(cfg: FLRunConfig, verbose: bool = False, *,
        mesh=None, client_axes=None) -> Dict[str, list]:
    """Drop-in replacement for the legacy ``run_fl`` loop: same history
    dict (entries at every ``eval_every``-th round plus the last), produced
    by a single scan-compiled call and ONE device->host transfer.  Async
    strategies route to `core/async_engine.run` (same history keys, plus
    buffer/staleness telemetry)."""
    if strat_lib.get(cfg.method).is_async:
        from repro.core import async_engine
        return async_engine.run(cfg, verbose=verbose, mesh=mesh,
                                client_axes=client_axes)
    final_state, outs = simulate(cfg, mesh=mesh, client_axes=client_axes)
    history = history_from_outputs(outs)            # the one transfer
    if verbose:
        k = 1 if strat_lib.get(cfg.method).centralized else cfg.num_clusters
        for r, a, l, t, e in zip(history["round"], history["acc"],
                                 history["loss"], history["time_s"],
                                 history["energy_j"]):
            print(f"[{cfg.method} K={k}] round {r:4d} "
                  f"acc={a:.3f} loss={l:.3f} T={t:.0f}s E={e:.1f}J")
    return history


def _vmapped_scan_fn(cfg: FLRunConfig):
    """Counted wrapper over the cached vmapped scan: the fleet sweep
    layer (`repro.fleet`) asserts one lower+compile per compile-cache
    equivalence class via ``engine.vmap_cache.hit/miss`` — the batched
    counterpart of ``api.aot_cache.hit/miss``."""
    misses0 = _vmapped_scan_fn_cached.cache_info().misses
    fn = _vmapped_scan_fn_cached(cfg)
    if _vmapped_scan_fn_cached.cache_info().misses > misses0:
        COUNTERS.inc("engine.vmap_cache.miss")
    else:
        COUNTERS.inc("engine.vmap_cache.hit")
    return fn


@functools.lru_cache(maxsize=32)
def _vmapped_scan_fn_cached(cfg: FLRunConfig):
    strategy = strat_lib.get(cfg.method)   # validate before tracing
    del strategy
    # the contact plan rides as a separate, non-batched argument: it is
    # seed-independent, so it is shared (broadcast) instead of stacked
    return jax.jit(jax.vmap(
        lambda s0, d, plan: _scan_fn(cfg)(s0, d._replace(plan=plan)),
        in_axes=(0, 0, None)))


def run_many_seeds(cfg: FLRunConfig,
                   seeds: Sequence[int]) -> Dict[str, np.ndarray]:
    """Multi-seed sweep: per-seed setups are stacked and the full round
    scan runs as ONE compiled ``vmap`` call over the seed axis.  The
    contact plan (when the strategy is visibility-gated) is built once
    and broadcast across the seed axis, not rebuilt or copied per seed.

    Returns per-round arrays of shape ``(num_seeds, rounds)`` — mask by
    ``evaluated`` to recover the eval-cadence history — plus per-seed
    re-cluster totals."""
    strategy = strat_lib.get(cfg.method)
    if strategy.is_async:
        raise NotImplementedError(
            "run_many_seeds is sync-only for now; vmap the async engine's "
            "scan directly or loop async_engine.run over seeds")
    if cfg.contact_slices or cfg.contact_factorized:
        raise ValueError(
            "contact_slices/contact_factorized are incompatible with "
            "run_many_seeds: both plan forms are seed-dependent (they "
            "bake in one seed's cluster layout), while the sweep shares "
            "a single plan across the seed axis. Use the full stored "
            "plan for sweeps.")
    plan = _plan_for(cfg, strategy)
    setups = [setup(cfg, int(s), contact_plan=plan) for s in seeds]
    state0 = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                    *[s for s, _ in setups])
    data = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[d._replace(plan=None) for _, d in setups])
    final_state, outs = _vmapped_scan_fn(cfg)(state0, data, plan)
    outs, _ = split_outputs(outs)       # telemetry (if on) is dropped:
    #                                     sweeps report trajectories only
    outs = jax.device_get(outs)
    return {
        "seeds": np.asarray(list(seeds)),
        "acc": np.asarray(outs.acc),
        "loss": np.asarray(outs.loss),
        "time_s": np.asarray(outs.time_s),
        "energy_j": np.asarray(outs.energy_j),
        "evaluated": np.asarray(outs.evaluated),
        "reclusters": np.asarray(outs.reclustered).sum(axis=1),
        "global_rounds": np.asarray(outs.did_global).sum(axis=1),
    }
