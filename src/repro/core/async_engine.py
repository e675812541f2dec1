"""Event-driven asynchronous FL engine: FedBuff-style buffered,
staleness-weighted aggregation as ONE compiled ``lax.scan`` — same
one-device-transfer discipline as the synchronous engine
(`core/engine.py`), which routes ``aggregation="async-buffered"``
strategies here automatically.

Why
---
FedHC's synchronous rounds idle every satellite on the slowest cluster
member and on ground-station windows.  The async engine removes the
barrier: each satellite runs on its own **virtual clock**, advanced by
the strategy cost model (compute + route time from the contact plan),
and the server side reacts to *events* instead of rounds.

Event semantics (one scan step = one event)
-------------------------------------------
1. **Pop** the earliest-deadline cohort: the ``cfg.async_cohort`` clients
   with the smallest clocks (a static ``lax.top_k``, so shapes never
   change).  The event time is the cohort's latest completion.
2. **Train** the cohort on the models they fetched at their previous
   restart (`_local_train` on the gathered sub-stack) — the training that
   notionally happened since the fetch is materialized at pop time.
3. **Contribute**: each update lands in its cluster's buffer with weight
   ``base_weight * s(tau)`` where ``tau = v_cluster - v_client`` is the
   on-device version-vector staleness and ``s`` the pluggable decay
   schedule (`core/staleness.py`).  For visibility-gated strategies the
   upload is validated against the contact plan **at the client's own
   clock** (`orbits/contact.route_to_ps_per_client`), not a global time;
   a member with no route keeps training (its previous pending
   contribution, if any, stays buffered).  A client popped again before
   its previous contribution flushed *supersedes* it (the buffer keeps at
   most one — the freshest — update per client).
4. **Flush**: any cluster whose buffer reached
   ``min(cfg.async_buffer, cluster size)`` replaces (or, with
   ``server_lr < 1``, mixes) its model with the buffered aggregate via
   the same one-hot segment-matmul math as the synchronous stage-1
   (`core/aggregation_spmd.buffered_flush_sharded`), bumping its model
   version.
5. **Stage-2** (hierarchical methods): once every non-empty cluster has
   committed ``cfg.rounds_per_global`` flushes since the last global, the
   cluster models aggregate globally (data-size weights, exactly the sync
   stage-2 math).  Visibility-gated strategies defer through the same
   ``pending_global`` carry as the sync engine; the contact window and
   exchange costs are evaluated at the *last* event time (``t_sim``), the
   async analog of the sync engine's start-of-round evaluation.
6. **Restart**: cohort members fetch the current cluster model (bumping
   their ``v_client``), and their clocks advance past the event by the
   inter-round gap plus their next round's cost, evaluated at the restart
   time.

Synchronous limit (pinned by ``tests/test_async_engine.py``)
------------------------------------------------------------
With ``async_cohort = async_buffer = num_clients`` and the ``constant``
staleness schedule, every event pops everyone, every buffer fills, and
every weight is exactly 1.0 — the engine takes a dedicated full-cohort
path (no gather/scatter, sync-style cost reduction) that reproduces the
synchronous trajectory **bit-for-bit**: same RNG stream, same
`_local_train`, same `aggregation.cluster_weights`/``cluster_aggregate``
calls, same cost expressions and addition order.

Mesh-awareness mirrors the sync engine: ``setup``/``simulate``/``run``
take ``mesh=``/``client_axes=``; the two client stacks (working models +
buffered contributions) and every per-client vector shard their leading
dim over the client axes, with the same ``with_sharding_constraint``
pins; cohort gathers/scatters lower to collectives under GSPMD.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import aggregation_spmd as agg_spmd
from repro.core import engine
from repro.core import staleness as stale_lib
from repro.core import strategies as strat_lib
from repro.core.engine import SimData
from repro.core.fedhc import FLRunConfig, _local_train
from repro.data.synthetic import client_batches, gather_samples, sample_rows
from repro.launch import mesh as mesh_lib
from repro.models.lenet import lenet_accuracy
from repro.obs.telemetry import Telemetry
from repro.obs.trace import phase_scope
from repro.orbits import contact as contact_lib
from repro.orbits import cost as cost_lib
from repro.orbits import topology as topo_lib
from repro.orbits.constellation import ground_station_position
from repro.orbits.links import LinkParams
from repro.sharding import rules as shard_rules


class AsyncState(NamedTuple):
    """Everything one async event mutates, as a scan carry."""
    work_params: Any           # (C, ...) model each client trains (its
    #                            last fetch from its cluster PS)
    contrib_params: Any        # (C, ...) last completed update per client,
    #                            buffered until its cluster flushes
    cluster_params: Any        # (K, ...) cluster/server models
    contrib_w: jnp.ndarray     # (C,) f32 staleness-decayed buffer weight
    #                            (0 = empty slot)
    losses: jnp.ndarray        # (C,) last training loss per client
    clock: jnp.ndarray         # (C,) f32 completion time of the round in
    #                            flight (the event queue)
    dur: jnp.ndarray           # (C,) f32 duration of the round in flight
    e_pending: jnp.ndarray     # (C,) f32 energy of the round in flight
    v_cluster: jnp.ndarray     # (K,) int32 cluster model version
    v_client: jnp.ndarray      # (C,) int32 version each client fetched
    commits: jnp.ndarray       # (K,) int32 flushes since the last global
    assignment: jnp.ndarray    # (C,) int32 static cluster id
    ps_index: jnp.ndarray      # (K,) int32 static cluster PS satellite
    rng: jax.Array             # loop key; per-event keys fold in the index
    t_sim: jnp.ndarray         # () f32 last event's restart time
    e_sim: jnp.ndarray         # () f32 cumulative energy (J)
    pending_global: jnp.ndarray  # () bool: stage-2 waiting for a window


class AsyncOutput(NamedTuple):
    """Per-event scan output; stacked over events = the full history."""
    acc: jnp.ndarray           # test accuracy (NaN on non-eval events)
    loss: jnp.ndarray          # mean of the per-client last-known losses
    time_s: jnp.ndarray        # simulated time after this event —
    #                            non-decreasing, not strictly increasing:
    #                            in the partial-cohort path two events
    #                            can land at the same instant (a cohort
    #                            clamped to the previous event's
    #                            global-exchange finish)
    energy_j: jnp.ndarray      # cumulative energy after this event
    evaluated: jnp.ndarray     # bool: acc is valid this event
    did_global: jnp.ndarray    # int32 0/1: stage-2 fired this event
    flushes: jnp.ndarray       # int32: cluster buffers flushed this event
    mean_tau: jnp.ndarray      # f32 mean staleness of accepted updates
    #                            (0.0 when none were accepted)


def _statics(cfg: FLRunConfig):
    """Resolve + validate the static async knobs for a config."""
    strategy = strat_lib.get(cfg.method)
    if not strategy.is_async:
        raise ValueError(f"{cfg.method!r} is a synchronous strategy; use "
                         f"repro.core.engine (which routes automatically)")
    c = cfg.num_clients
    cohort = cfg.async_cohort if cfg.async_cohort > 0 else c
    if not 1 <= cohort <= c:
        raise ValueError(f"async_cohort={cfg.async_cohort} must be in "
                         f"[1, num_clients={c}]")
    buffer = cfg.async_buffer if cfg.async_buffer > 0 else cohort
    if cfg.staleness not in stale_lib.names():
        raise ValueError(f"unknown staleness schedule {cfg.staleness!r}; "
                         f"registered: {stale_lib.names()}")
    k = 1 if strategy.flat else cfg.num_clusters
    return strategy, cohort, buffer, k


def _member_costs(cfg: FLRunConfig, strategy, plan, assignment, ps_index,
                  t, data_sizes, freqs, constellation, model_bits, lp, cp):
    """Per-client (duration, energy) of one local round starting at the
    scalar time ``t`` — the same expressions the sync engine reduces to a
    makespan (`orbits/cost.cluster_member_costs` and friends), kept as
    vectors so each client's own clock can advance independently."""
    if strategy.visibility_gated:
        if isinstance(plan, contact_lib.ClusterContactPlan):
            _, _, tpb_to_ps, _ = contact_lib.lookup_sliced(plan, t)
        else:
            _, _, tpb = contact_lib.lookup(plan, t)
            tpb_to_ps = tpb[jnp.arange(cfg.num_clients),
                            ps_index[assignment]]
        return cost_lib.routed_cluster_member_costs(
            tpb_to_ps, jnp.isfinite(tpb_to_ps), data_sizes, freqs,
            model_bits=model_bits, lp=lp, cp=cp)
    positions = constellation.positions(t)
    ps_positions = positions[ps_index][assignment]
    return cost_lib.cluster_member_costs(
        positions, ps_positions, data_sizes, freqs,
        model_bits=model_bits, lp=lp, cp=cp)


def _model_bits(work_params, num_clients: int) -> float:
    leaves = jax.tree_util.tree_leaves(work_params)
    return sum(x.size for x in leaves) / num_clients * 32.0


def _place(cfg: FLRunConfig, strategy, state0: AsyncState, data: SimData,
           mesh, caxes) -> tuple[AsyncState, SimData]:
    """Mesh layout: both client stacks + every per-client vector shard
    their leading dim over the client axes; cluster models, version
    vectors and scalars are replicated; SimData/plan placement is shared
    with the sync engine (`engine._data_shardings`)."""
    mesh_lib.validate_client_sharding(mesh, caxes, cfg.num_clients)
    repl = NamedSharding(mesh, P())
    cvec = NamedSharding(
        mesh, shard_rules.client_spec(mesh, caxes, cfg.num_clients))
    pspecs = shard_rules.tree_param_specs(
        state0.work_params, mesh, client_axes=caxes, client_stacked=True)
    stack_sh = shard_rules.tree_shardings(pspecs, mesh)
    krepl = jax.tree_util.tree_map(lambda _: repl, state0.cluster_params)
    state_sh = AsyncState(
        work_params=stack_sh, contrib_params=stack_sh, cluster_params=krepl,
        contrib_w=cvec, losses=cvec, clock=cvec, dur=cvec, e_pending=cvec,
        v_cluster=repl, v_client=cvec, commits=repl, assignment=repl,
        ps_index=repl, rng=repl, t_sim=repl, e_sim=repl,
        pending_global=repl)
    data_sh = engine._data_shardings(cfg, strategy, data, mesh, caxes)
    return jax.device_put(state0, state_sh), jax.device_put(data, data_sh)


def setup(cfg: FLRunConfig, seed: Optional[int] = None,
          contact_plan=None, mesh=None,
          client_axes=None) -> tuple[AsyncState, SimData]:
    """One-time experiment setup.  Delegates data/model/clustering init to
    ``engine.setup`` (identical RNG stream layout — the basis of the
    sync-equivalence pin), then builds the event-queue state: every
    client's first round starts at t=0, so its initial clock/energy are
    the t=0 member costs."""
    strategy, cohort, buffer, k = _statics(cfg)
    sync_state, data = engine.setup(cfg, seed, contact_plan=contact_plan)
    c = cfg.num_clients

    assignment = sync_state.assignment
    ps_index = sync_state.ps_index[:k]
    # all rows of the initial stack are w0, so slicing k rows = k copies
    cluster_params = jax.tree_util.tree_map(lambda x: x[:k],
                                            sync_state.params)
    lp, cp = LinkParams(), cost_lib.ComputeParams()
    constellation = engine._constellation_for(c)
    dur0, e0 = _member_costs(
        cfg, strategy, data.plan, assignment, ps_index, jnp.float32(0.0),
        data.data_sizes, data.freqs, constellation,
        _model_bits(sync_state.params, c), lp, cp)
    state0 = AsyncState(
        work_params=sync_state.params, contrib_params=sync_state.params,
        cluster_params=cluster_params,
        contrib_w=jnp.zeros((c,), jnp.float32),
        losses=jnp.ones((c,), jnp.float32),
        clock=dur0, dur=dur0, e_pending=e0,
        v_cluster=jnp.zeros((k,), jnp.int32),
        v_client=jnp.zeros((c,), jnp.int32),
        commits=jnp.zeros((k,), jnp.int32),
        assignment=assignment, ps_index=ps_index, rng=sync_state.rng,
        t_sim=jnp.float32(0.0), e_sim=jnp.float32(0.0),
        pending_global=jnp.bool_(False))
    if mesh is not None:
        state0, data = _place(cfg, strategy, state0, data, mesh,
                              engine._resolve_client_axes(mesh, client_axes))
    return state0, data


def _scan_fn(cfg: FLRunConfig, mesh=None, client_axes=None):
    """Build (and cache) the jitted ``(state0, data) -> (state, outputs)``
    event scan for a config (same canonicalization as the sync engine)."""
    return _scan_fn_cached(cfg, mesh,
                           engine._resolve_client_axes(mesh, client_axes))


@functools.lru_cache(maxsize=32)
def _scan_fn_cached(cfg: FLRunConfig, mesh, client_axes):
    strategy, cohort, buffer, k = _statics(cfg)
    c = cfg.num_clients
    full = cohort == c          # full-cohort: the synchronous limit —
    #                             no gather/scatter, sync-style cost
    #                             reduction, bit-compatible trajectory
    m = cfg.rounds_per_global
    img_shape = (cfg.dataset.img, cfg.dataset.img, cfg.dataset.channels)
    constellation = engine._constellation_for(c)
    lp, cp = LinkParams(), cost_lib.ComputeParams()
    use_pallas = cfg.use_pallas_kernels
    telem_on = cfg.telemetry    # extra scan outputs only; the event
    #                             trajectory is bit-identical on or off

    caxes = engine._resolve_client_axes(mesh, client_axes)
    sharded = mesh is not None
    if sharded:
        mesh_lib.validate_client_sharding(mesh, caxes, c)
        cvec_sharding = NamedSharding(
            mesh, shard_rules.client_spec(mesh, caxes, c))

        def shard_clients(x):
            return jax.lax.with_sharding_constraint(x, cvec_sharding)
    else:
        def shard_clients(x):
            return x

    def run_scan(state0: AsyncState, data: SimData):
        with phase_scope("async_event/batches"):
            rows = sample_rows(data.images)     # once a call, not an event
        model_bits = _model_bits(state0.work_params, c)
        if sharded:
            pspecs = shard_rules.tree_param_specs(
                state0.work_params, mesh, client_axes=caxes,
                client_stacked=True)
            param_shardings = shard_rules.tree_shardings(pspecs, mesh)

            def shard_stack(tree):
                return jax.lax.with_sharding_constraint(tree,
                                                        param_shardings)
        else:
            def shard_stack(tree):
                return tree

        def member_costs(t):
            return _member_costs(cfg, strategy, data.plan, state0.assignment,
                                 state0.ps_index, t, data.data_sizes,
                                 data.freqs, constellation, model_bits,
                                 lp, cp)

        def event_step(state, step):
            # every op of the event sits under one async_event/* scope:
            # select, batches, local_train, contribute, aggregate (flush,
            # global, fetch), orbit (costs, route gating), eval (and
            # telemetry)
            with phase_scope("async_event/batches"):
                r_rnd = jax.random.fold_in(state.rng, step)

            # ---- 1. pop the earliest-deadline cohort ---------------------
            with phase_scope("async_event/select"):
                if full:
                    in_cohort = jnp.ones((c,), bool)
                else:
                    _, idx = jax.lax.top_k(-state.clock, cohort)
                    cohort_idx = jnp.sort(idx)     # ascending client order
                    in_cohort = jnp.zeros((c,), bool).at[cohort_idx].set(
                        True)
                    t_event = jnp.max(jnp.where(in_cohort, state.clock,
                                                -jnp.inf))

            # ---- 2. train the cohort on its fetched bases ----------------
            if full:
                with phase_scope("async_event/batches"):
                    imgs, labs = client_batches(rows, data.labels,
                                                data.client_idx, r_rnd,
                                                cfg.batch_size, img_shape)
                    imgs, labs = shard_clients(imgs), shard_clients(labs)
                with phase_scope("async_event/local_train"):
                    trained, l_new = _local_train(
                        state.work_params, imgs, labs, lr=cfg.lr,
                        steps=cfg.local_steps,
                        microbatch=cfg.client_microbatch,
                        client_shards=(shard_rules.axis_size(mesh, caxes)
                                       if sharded else 1))
                    trained = shard_stack(trained)
                    losses = shard_clients(l_new)
            else:
                # full-width batch *indices* (bit-stable vs the cohort
                # composition), but only the cohort's samples are gathered
                # and only the cohort trains
                with phase_scope("async_event/batches"):
                    spc = data.client_idx.shape[1]
                    picks = jax.random.randint(r_rnd, (c, cfg.batch_size),
                                               0, spc)
                    flat = jnp.take_along_axis(data.client_idx, picks,
                                               axis=1)
                    flat_c = flat[cohort_idx]
                    imgs = gather_samples(rows, flat_c, img_shape)
                    labs = data.labels[flat_c]
                    base = jax.tree_util.tree_map(lambda x: x[cohort_idx],
                                                  state.work_params)
                # cohort stacks are gather products with no pinned layout,
                # so the microbatch scan uses the unsharded decomposition
                with phase_scope("async_event/local_train"):
                    trained, l_c = _local_train(
                        base, imgs, labs, lr=cfg.lr, steps=cfg.local_steps,
                        microbatch=cfg.client_microbatch)
                    losses = shard_clients(
                        state.losses.at[cohort_idx].set(l_c))

            # ---- 3. contribute (per-client-clock gated, staleness-weighted)
            with phase_scope("async_event/contribute"):
                tau = (state.v_cluster[state.assignment]
                       - state.v_client).astype(jnp.float32)      # (C,)
                s = stale_lib.decay(cfg.staleness, tau, a=cfg.staleness_a,
                                    b=cfg.staleness_b)
            if strategy.visibility_gated:
                # the upload happened at the client's OWN clock — validate
                # its route against the plan row at that time, not t_event
                with phase_scope("async_event/orbit"):
                    tpb_up = contact_lib.route_to_ps_per_client(
                        data.plan, state.clock,
                        state.ps_index[state.assignment])
                    ok = in_cohort & jnp.isfinite(tpb_up)
            else:
                ok = in_cohort
            with phase_scope("async_event/contribute"):
                contrib_w = jnp.where(ok, s, state.contrib_w)
                if full:
                    contrib = jax.tree_util.tree_map(
                        lambda t_, o: jnp.where(
                            ok.reshape((-1,) + (1,) * (t_.ndim - 1)), t_, o),
                        trained, state.contrib_params)
                else:
                    ok_c = ok[cohort_idx]

                    def scatter_ok(o, t_):
                        keep = jnp.where(
                            ok_c.reshape((-1,) + (1,) * (t_.ndim - 1)),
                            t_, o[cohort_idx])
                        return o.at[cohort_idx].set(keep)

                    contrib = jax.tree_util.tree_map(
                        scatter_ok, state.contrib_params, trained)
                contrib = shard_stack(contrib)
                n_ok = jnp.sum(ok.astype(jnp.float32))
                mean_tau = (jnp.sum(jnp.where(ok, tau, 0.0))
                            / jnp.maximum(n_ok, 1.0))

            # ---- 4. flush full buffers (one-hot segment-matmul math) -----
            with phase_scope("async_event/aggregate/flush"):
                one_hot = jax.nn.one_hot(state.assignment, k,
                                         dtype=jnp.float32)       # (C,K)
                member_count = jnp.sum(one_hot, axis=0)           # (K,)
                buf_count = agg.segment_sum(
                    one_hot, (contrib_w > 0).astype(jnp.float32))
                flush = ((buf_count >= jnp.minimum(float(buffer),
                                                   member_count))
                         & (member_count > 0))
                cluster_models = agg_spmd.buffered_flush_sharded(
                    contrib, losses, data.data_sizes, state.assignment, k,
                    contrib_w, flush, state.cluster_params,
                    loss_weighted=strategy.loss_weighted,
                    server_lr=cfg.server_lr, use_pallas=use_pallas)
                flush_i = flush.astype(jnp.int32)
                v_cluster = state.v_cluster + flush_i
                commits = state.commits + flush_i
                contrib_w = jnp.where(flush[state.assignment], 0.0,
                                      contrib_w)

            # ---- 5. buffered stage-2 across clusters ---------------------
            if k == 1:
                # flat (fedbuff): the single buffer IS the server
                do_global = jnp.bool_(False)
                pending_next = state.pending_global
                t_g = e_g = jnp.float32(0.0)
            else:
                with phase_scope("async_event/aggregate/global"):
                    active = member_count > 0
                    due = (jnp.all(jnp.where(active, commits >= m, True))
                           | state.pending_global)
                # window + exchange costs as of the last event (t_sim):
                # the async analog of the sync engine's start-of-round
                # evaluation (and bit-compatible with it in the
                # full-cohort limit)
                with phase_scope("async_event/orbit"):
                    if strategy.visibility_gated:
                        if isinstance(data.plan,
                                      contact_lib.ClusterContactPlan):
                            gs_vis, gs_dist, _, ps_rows = \
                                contact_lib.lookup_sliced(data.plan,
                                                          state.t_sim)
                        else:
                            gs_vis, gs_dist, tpb = contact_lib.lookup(
                                data.plan, state.t_sim)
                            ps_rows = tpb[state.ps_index]
                        worst = jnp.max(ps_rows, axis=0)          # (C,)
                        score = jnp.where(gs_vis, worst, jnp.inf)
                        gateway = jnp.argmin(score).astype(jnp.int32)
                        window = jnp.isfinite(score[gateway])
                        t_g, e_g = cost_lib.routed_ground_round_costs(
                            ps_rows[:, gateway], gs_dist[gateway],
                            model_bits=model_bits, lp=lp)
                    else:
                        positions = constellation.positions(state.t_sim)
                        gs = ground_station_position(t_s=state.t_sim)
                        window = jnp.bool_(True)
                        t_g, e_g = cost_lib.ground_round_costs(
                            positions[state.ps_index], gs,
                            model_bits=model_bits, lp=lp)
                with phase_scope("async_event/aggregate/global"):
                    do_global = due & window
                    pending_next = due & ~window
                    dk = agg.segment_sum(
                        one_hot, data.data_sizes.astype(jnp.float32))
                    cluster_models = jax.lax.cond(
                        do_global,
                        lambda cm: agg.broadcast_global(
                            agg.global_aggregate(cm, dk), k),
                        lambda cm: cm, cluster_models)
                    v_cluster = v_cluster + do_global.astype(jnp.int32)
                    commits = jnp.where(do_global, 0, commits)

            # ---- 6. costs + restart the cohort ---------------------------
            do_g = do_global
            with phase_scope("async_event/orbit"):
                t_g_sel = jnp.where(do_g, t_g, 0.0)
                if full:
                    # sync-identical reduction and addition order
                    t_r = jnp.max(jnp.where(in_cohort, state.dur, 0.0))
                    t_restart = (state.t_sim + (t_r + t_g_sel)
                                 + cfg.round_minutes * 60.0)
                else:
                    # clamp to the last event: a cohort restarting right
                    # after a global-exchange event must not report time
                    # backwards
                    t_restart = jnp.maximum(
                        state.t_sim,
                        t_event + t_g_sel + cfg.round_minutes * 60.0)
                e_event = jnp.sum(jnp.where(in_cohort, state.e_pending,
                                            0.0))
                e_new = state.e_sim + (e_event + jnp.where(do_g, e_g, 0.0))
                dur_next, e_next = member_costs(t_restart)
                new_clock = jnp.where(in_cohort, t_restart + dur_next,
                                      state.clock)
                new_dur = jnp.where(in_cohort, dur_next, state.dur)
                new_e_pending = jnp.where(in_cohort, e_next,
                                          state.e_pending)

            # ---- 7. fetch: cohort re-syncs to its cluster model ----------
            with phase_scope("async_event/aggregate/fetch"):
                fetched = agg.broadcast_clusters(cluster_models,
                                                 state.assignment)
                work = jax.tree_util.tree_map(
                    lambda f, w: jnp.where(
                        in_cohort.reshape((-1,) + (1,) * (f.ndim - 1)),
                        f, w),
                    fetched, state.work_params)
                work = shard_stack(work)
                v_client = jnp.where(in_cohort,
                                     v_cluster[state.assignment],
                                     state.v_client)

            # ---- 8. eval + outputs ---------------------------------------
            with phase_scope("async_event/eval"):
                evaluated = (((step + 1) % cfg.eval_every == 0)
                             | (step == cfg.rounds - 1))
                acc = jax.lax.cond(
                    evaluated,
                    lambda _: lenet_accuracy(
                        jax.tree_util.tree_map(
                            lambda x: jnp.mean(x.astype(jnp.float32), 0),
                            work),
                        data.test_x, data.test_y),
                    lambda _: jnp.float32(jnp.nan), None)
                loss_val = jnp.mean(losses)

            new_state = AsyncState(
                work_params=work, contrib_params=contrib,
                cluster_params=cluster_models, contrib_w=contrib_w,
                losses=losses, clock=new_clock, dur=new_dur,
                e_pending=new_e_pending, v_cluster=v_cluster,
                v_client=v_client, commits=commits,
                assignment=state.assignment, ps_index=state.ps_index,
                rng=state.rng, t_sim=t_restart, e_sim=e_new,
                pending_global=pending_next)
            with phase_scope("async_event/eval"):
                out = AsyncOutput(acc, loss_val, t_restart, e_new, evaluated,
                                  do_g.astype(jnp.int32), jnp.sum(flush_i),
                                  mean_tau)
            if not telem_on:
                return new_state, out

            # ---- 9. telemetry (outputs only, nothing re-enters the carry)
            with phase_scope("async_event/telemetry"):
                n_ok_i = n_ok.astype(jnp.int32)
                stale_min = jnp.where(
                    n_ok > 0, jnp.min(jnp.where(ok, tau, jnp.inf)), 0.0)
                stale_max = jnp.where(
                    n_ok > 0, jnp.max(jnp.where(ok, tau, -jnp.inf)), 0.0)
                # compute energy of the cohort's materialized rounds is
                # time-independent, so subtracting it from the event's
                # energy delta splits compute vs comm exactly
                e_cmp = jnp.sum(
                    jnp.where(in_cohort, cost_lib.compute_energy_j(
                        data.data_sizes, data.freqs, cp), 0.0))
                bits1 = model_bits * (n_ok + float(cohort))   # up + fetch
                bits2 = jnp.where(do_g,
                                  jnp.float32(2.0 * model_bits * k), 0.0)
                if strategy.visibility_gated:
                    # hop counts sampled at the event time (per-client
                    # upload clocks are gated exactly via the plan; the
                    # hop telemetry is the event-anchored view)
                    pos_t = constellation.positions(state.t_sim)
                    adj = topo_lib.isl_adjacency(pos_t,
                                                 cfg.isl_max_range_km)
                    hrows = topo_lib.hop_rows(adj, state.ps_index,
                                              cfg.isl_max_hops)
                    hops = hrows[state.assignment, jnp.arange(c)]
                    routed = ok & jnp.isfinite(hops)
                    n_routed = jnp.sum(routed.astype(jnp.float32))
                    hops_mean = (jnp.sum(jnp.where(routed, hops, 0.0))
                                 / jnp.maximum(n_routed, 1.0))
                    hops_max = jnp.max(jnp.where(routed, hops, 0.0))
                else:
                    hops_mean = hops_max = jnp.float32(0.0)
                telem = Telemetry(
                    cohort_size=jnp.int32(cohort), accepted=n_ok_i,
                    cluster_fill=buf_count,
                    stale_min=stale_min, stale_mean=mean_tau,
                    stale_max=stale_max,
                    flushes=jnp.sum(flush_i),
                    did_global=do_g.astype(jnp.int32),
                    reclustered=jnp.int32(0),
                    bits_stage1=bits1, bits_stage2=bits2,
                    t_round_s=t_restart - state.t_sim,
                    e_compute_j=e_cmp,
                    e_comm_j=(e_new - state.e_sim) - e_cmp,
                    hops_mean=hops_mean, hops_max=hops_max)
            return new_state, (out, telem)

        return jax.lax.scan(event_step, state0, jnp.arange(cfg.rounds))

    return jax.jit(run_scan)


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------


def simulate(cfg: FLRunConfig, seed: Optional[int] = None, *,
             mesh=None, client_axes=None):
    """One compiled run -> (final AsyncState, stacked AsyncOutput) on
    device.  ``cfg.rounds`` counts *events* (cohort pops), so matching the
    synchronous engine's total client-rounds takes
    ``rounds_sync * num_clients / async_cohort`` events."""
    client_axes = engine._resolve_client_axes(mesh, client_axes)
    state0, data = setup(cfg, seed, mesh=mesh, client_axes=client_axes)
    return _scan_fn(cfg, mesh, client_axes)(state0, data)


def history_from_outputs(outs: AsyncOutput) -> Dict[str, list]:
    """Host-side history dict from a stacked :class:`AsyncOutput` — the
    eval-point extraction is shared with the sync engine
    (`engine.eval_point_lists`), plus the async telemetry totals.  A
    telemetry-carrying ``(AsyncOutput, Telemetry)`` pair is split and the
    telemetry dropped (`repro.api.run` extracts it separately)."""
    outs, _ = engine.split_outputs(outs)
    outs, history = engine.eval_point_lists(outs)
    history["reclusters"] = 0                # static layout by construction
    history["global_rounds"] = int(np.sum(outs.did_global))
    history["flushes"] = int(np.sum(outs.flushes))
    history["mean_staleness"] = float(np.mean(outs.mean_tau))
    return history


def run(cfg: FLRunConfig, verbose: bool = False, *,
        mesh=None, client_axes=None) -> Dict[str, list]:
    """Same history layout as ``engine.run`` (entries at every
    ``eval_every``-th event plus the last; ONE device->host transfer),
    plus async telemetry: total buffer ``flushes`` and the event-averaged
    ``mean_staleness`` of accepted contributions."""
    final_state, outs = simulate(cfg, mesh=mesh, client_axes=client_axes)
    history = history_from_outputs(outs)            # the one transfer
    if verbose:
        for r, a, l, t, e in zip(history["round"], history["acc"],
                                 history["loss"], history["time_s"],
                                 history["energy_j"]):
            print(f"[{cfg.method} async] event {r:5d} "
                  f"acc={a:.3f} loss={l:.3f} T={t:.0f}s E={e:.1f}J")
    return history
