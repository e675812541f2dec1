"""FedHC simulation driver — Algorithm 1 end-to-end, plus the three
comparative methods (C-FedAvg, H-BASE, FedCE) on the same substrate.

The driver couples:
  * the LeNet FL workload (paper §IV-A) on synthetic non-IID data,
  * the orbital simulator (positions -> visibility/dropout -> link rates),
  * the two-stage aggregation (core/aggregation.py),
  * MAML re-clustering (core/maml.py),
  * the Eq. 7-10 time/energy accounting (orbits/cost.py).

Methods:
  fedhc        : position k-means clusters + PS selection, loss-weighted
                 stage-1, stage-2 every m rounds, MAML on re-cluster.
  fedhc-nomaml : ablation — re-clusters but new members copy the cluster
                 model cold.
  h-base       : random static clusters, data-size weights, no re-cluster.
  fedce        : clusters on label-distribution (Dirichlet mixture) space,
                 data-size weights, no MAML.
  c-fedavg     : centralized — raw data to one satellite server (K=1).
  fedspace     : engine-only — FedSpace-style contact-window-scheduled
                 global aggregation over the precomputed contact plan.
  isl-onboard  : engine-only — no ground station; inter-cluster consensus
                 over multi-hop ISL routes between cluster PSs.
  fedbuff      : engine-only — flat single-server buffered async with
                 staleness-decay weights (event engine, per-client clocks).
  fedhc-async  : engine-only — per-cluster buffered async stage-1 +
                 buffered stage-2 across PSs.
  fedspace-async: engine-only — buffered async gated by the contact plan
                 at each client's own clock.

``run_fl`` is now a thin compatibility wrapper over the scan-compiled
round engine (`core/engine.py`), which executes the whole multi-round
simulation as one XLA program driven by the `core/strategies.py` registry.
The original host-side Python loop is kept as ``run_fl_legacy`` — it is the
semantic oracle the engine parity tests check against.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core import clustering as cl
from repro.core import maml as maml_lib
from repro.core import strategies as strat_lib
from repro.data.synthetic import (DatasetSpec, MNIST_LIKE, client_batches,
                                  dirichlet_partition, make_split,
                                  sample_rows)
from repro.models.lenet import init_lenet, lenet_accuracy, lenet_loss
from repro.orbits import cost as cost_lib
from repro.orbits.constellation import Constellation, ground_station_position
from repro.orbits.links import LinkParams

class _MethodsView:
    """Live, registry-ordered view of every registered method name.

    The old module-level ``METHODS = strat_lib.names()`` was an
    import-time snapshot that went stale whenever a strategy registered
    later (benchmarks and tests register variants at runtime).  This view
    reads the registry on every access, so ``"x" in METHODS``,
    iteration, ``len`` and indexing always reflect the current registry.
    Call :func:`methods` (or ``tuple(METHODS)``) for a plain tuple."""

    def _names(self) -> tuple:
        return strat_lib.names()

    def __iter__(self):
        return iter(self._names())

    def __len__(self) -> int:
        return len(self._names())

    def __getitem__(self, i):
        return self._names()[i]

    def __contains__(self, method) -> bool:
        return method in self._names()

    def __eq__(self, other):
        try:
            return tuple(self) == tuple(other)
        except TypeError:             # non-iterable: not equal, not an error
            return NotImplemented

    def __hash__(self):
        return hash(tuple(self._names()))

    def __repr__(self) -> str:
        return f"METHODS{self._names()!r}"


METHODS = _MethodsView()      # every registered method (paper five +
#                               connectivity/async variants), live view


def methods() -> tuple:
    """Snapshot of the registered method names (registry-ordered)."""
    return strat_lib.names()


@dataclass(frozen=True)
class FLRunConfig:
    method: str = "fedhc"
    num_clients: int = 64
    num_clusters: int = 4                 # K
    rounds: int = 150
    rounds_per_global: int = 5            # m
    local_steps: int = 2                  # SGD steps per round (lambda)
    batch_size: int = 64
    lr: float = 0.01
    dropout_threshold: float = 0.5        # Z
    maml_alpha: float = 1e-3
    maml_beta: float = 1e-3
    dataset: DatasetSpec = MNIST_LIKE
    samples_per_client: int = 128
    dirichlet_alpha: float = 0.5
    eval_every: int = 5
    eval_size: int = 1024
    seed: int = 0
    round_minutes: float = 1.0            # orbital time advanced per round
    # ---- time-varying connectivity (strategies with connectivity != ----
    # ---- "always"; ignored by the five always-up paper methods) --------
    contact_dt_s: float = 60.0            # contact-plan sample cadence
    gs_min_elevation_deg: float = 10.0    # ground-station elevation mask
    isl_max_range_km: float = 8000.0      # ISL terminal slant-range limit
    isl_max_hops: int = 8                 # route relaxation hop bound
    # ---- paper-scale execution (engine-only knobs; the legacy loop -----
    # ---- ignores both) -------------------------------------------------
    contact_dtype: str = "float32"        # ContactPlan isl_tpb storage:
    #                                       "float32" | "bfloat16" (halves
    #                                       the (T,N,N) route table at
    #                                       N=800; upcast at lookup)
    use_pallas_kernels: bool = False      # route the scan hot path through
    #                                       the Pallas kernels (kmeans
    #                                       assignment + stage-1 weighted
    #                                       aggregation; interpreted
    #                                       off-TPU)
    contact_slices: bool = False          # store only member->PS + PS-row
    #                                       routes ((T,N)+(T,K,N)) instead
    #                                       of the full (T,N,N) table;
    #                                       needs a static cluster layout
    #                                       (recluster="never") and is
    #                                       per-seed (run_many_seeds /
    #                                       api.run_sweep reject it)
    contact_factorized: bool = False      # store NO routes: recompute the
    #                                       member->PS + PS-row slices
    #                                       inside the scan from orbital
    #                                       geometry (O(N) plan storage;
    #                                       orbits/contact.
    #                                       FactorizedContactPlan).  Same
    #                                       static-layout + per-seed
    #                                       limits as contact_slices, and
    #                                       sync-engine only (the async
    #                                       per-client clocks would need
    #                                       one recompute per client)
    telemetry: bool = False               # emit the typed per-round
    #                                       repro.obs.Telemetry pytree as
    #                                       extra scan outputs (rides the
    #                                       one end-of-run transfer; the
    #                                       trajectory is bit-identical
    #                                       on or off).  Engine-only; the
    #                                       legacy loop ignores it
    client_microbatch: int = 0            # scan per-client work (local
    #                                       training, MAML re-cluster) over
    #                                       client sub-blocks of this size
    #                                       instead of one (C, ...) vmap —
    #                                       caps activation memory so
    #                                       clients-per-device can climb
    #                                       past 100 (0 = full vmap; equal
    #                                       to float rounding either way)
    # ---- asynchronous buffered aggregation (strategies with ------------
    # ---- aggregation="async-buffered"; ignored by sync methods) --------
    async_cohort: int = 0                 # clients popped per event
    #                                       (0 => num_clients: sync-like)
    async_buffer: int = 0                 # per-cluster flush threshold
    #                                       (0 => cohort size; a cluster
    #                                       smaller than the threshold
    #                                       flushes when ALL its members
    #                                       have contributed)
    staleness: str = "polynomial"         # staleness-decay schedule
    #                                       (core/staleness.py registry)
    staleness_a: float = 0.5              # decay exponent / slope
    staleness_b: float = 4.0              # hinge grace window (versions)
    server_lr: float = 1.0                # flush mixing rate (1.0 =
    #                                       replace with the buffered agg)

    def to_scenario(self):
        """The typed :class:`repro.core.scenario.Scenario` equivalent of
        this flat config (the composable-spec API; `repro.api.run` runs
        it).  Cross-field validation happens at Scenario construction, so
        an invalid flat combination raises a clear ``ValueError`` here."""
        from repro.core.scenario import Scenario
        return Scenario.from_flat(self)


# --------------------------------------------------------------------------


def _client_blocks(c: int, microbatch: int, client_shards: int = 1):
    """``(to_blocks, from_blocks)`` that split a leading client dim of
    size ``c`` into ``(nb, microbatch, ...)`` scan blocks and back, or
    ``None`` when ``microbatch`` is 0 or covers every client (one vmap).

    Under client-axis SPMD (``client_shards=S``, the client-axis size)
    each block takes m/S clients from EVERY shard — reshape/transpose
    moves that stay device-local — so all S devices stay busy every
    block.  That decomposition needs ``m % S == 0`` and
    ``(C/S) % (m/S) == 0`` (raised here otherwise; `core/scenario.py`
    validates the same at construction).  Unsharded, a non-divisor
    remainder is handled by wrap-padding the last block (duplicate work,
    discarded — results stay exact)."""
    mb, s = int(microbatch), max(1, int(client_shards))
    if not mb or mb >= c:
        return None

    if s > 1:
        if mb % s or (c // s) % (mb // s):
            raise ValueError(
                f"client_microbatch={mb} does not decompose device-locally "
                f"over {s} client shards: need microbatch % shards == 0 "
                f"and (num_clients//shards) % (microbatch//shards) == 0 "
                f"(num_clients={c})")
        nb, lmb = c // mb, mb // s

        def to_blocks(x):
            x = x.reshape((s, nb, lmb) + x.shape[1:])
            x = jnp.swapaxes(x, 0, 1)                # (nb, s, lmb, ...)
            return x.reshape((nb, mb) + x.shape[3:])

        def from_blocks(x):
            x = x.reshape((nb, s, lmb) + x.shape[2:])
            x = jnp.swapaxes(x, 0, 1)                # (s, nb, lmb, ...)
            return x.reshape((c,) + x.shape[3:])
    else:
        nb = -(-c // mb)
        n_pad = nb * mb - c

        def to_blocks(x):
            if n_pad:
                x = jnp.concatenate([x, x[:n_pad]], axis=0)
            return x.reshape((nb, mb) + x.shape[1:])

        def from_blocks(x):
            return x.reshape((nb * mb,) + x.shape[2:])[:c]
    return to_blocks, from_blocks


def _vmap_clients(fn, args, *, microbatch: int = 0, client_shards: int = 1):
    """``jax.vmap(fn)(*args)`` over the leading client dim of every leaf
    of ``args``, or — with ``microbatch=m`` — a ``lax.scan`` over
    ceil(C/m) m-client blocks, each block a vmap (`_client_blocks`).
    The per-client math is the same either way; peak activation memory
    drops from O(C * acts) to O(m * acts)."""
    c = jax.tree_util.tree_leaves(args)[0].shape[0]
    blocks = _client_blocks(c, microbatch, client_shards)
    if blocks is None:
        return jax.vmap(fn)(*args)
    to_blocks, from_blocks = blocks

    def block_step(_, xs):
        return None, jax.vmap(fn)(*xs)

    _, out = jax.lax.scan(block_step, None,
                          jax.tree_util.tree_map(to_blocks, args))
    return jax.tree_util.tree_map(from_blocks, out)


def _local_train(params_stack, images, labels, lr, steps, *,
                 microbatch: int = 0, client_shards: int = 1):
    """Per-client local SGD: `steps` steps each.  Returns (params, loss).

    ``microbatch=0`` (default) vmaps over the whole (C, ...) stack at
    once; ``microbatch=m`` scans over m-client sub-blocks instead
    (`_vmap_clients`).  Results agree to float rounding, not bit for bit:
    XLA may pick a different reduction order for a different batch
    shape.  At paper scale the full-vmap activations do not fit
    a device's memory; microbatching bounds them and is what lets
    clients-per-device climb past 100.  ``client_shards`` is the
    client-axis size under SPMD (see `_client_blocks`)."""

    def one_client(p, imgs, labs):
        def body(p, _):
            l, g = jax.value_and_grad(lenet_loss)(p, (imgs, labs))
            p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
            return p, l
        p, losses = jax.lax.scan(body, p, None, length=steps)
        return p, losses[-1]

    return _vmap_clients(one_client, (params_stack, images, labels),
                         microbatch=microbatch, client_shards=client_shards)


def _meta_update_clusters(cluster_models, assignment, images, labels, *,
                          k, alpha, beta, microbatch: int = 0,
                          client_shards: int = 1):
    """Eq. 16-17 per cluster: inner-adapt each member's copy of its cluster
    model on its own batch, outer-update the cluster model with the summed
    post-adaptation gradients (membership-masked).  The per-member
    second-order gradients run over the same client blocks as
    `_local_train` (``microbatch``/``client_shards``); the membership sum
    runs once over the whole (C, ...) gradient stack."""

    def task_grad(model, imgs, labs):
        adapted = maml_lib.inner_adapt(lenet_loss, model, (imgs, labs), alpha)
        return jax.grad(lenet_loss)(adapted, (imgs, labs))

    member_models = agg.broadcast_clusters(cluster_models, assignment)
    grads = _vmap_clients(task_grad, (member_models, images, labels),
                          microbatch=microbatch,
                          client_shards=client_shards)              # (C,...)
    one_hot = jax.nn.one_hot(assignment, k, dtype=jnp.float32)      # (C,K)

    def per_cluster(g):
        flat = g.reshape(g.shape[0], -1)
        summed = agg.segment_sum(one_hot, flat)                     # (K,P)
        return summed.reshape((k,) + g.shape[1:])

    cluster_grads = jax.tree_util.tree_map(per_cluster, grads)
    return jax.tree_util.tree_map(lambda m, g: m - beta * g,
                                  cluster_models, cluster_grads)


def _adapt_members(models, images, labels, *, alpha, microbatch: int = 0,
                   client_shards: int = 1):
    """§III-C hand-off: each member takes one MAML inner step on its own
    batch from its inherited model, over the `_local_train` client
    blocks."""
    return _vmap_clients(
        lambda m, i, l: maml_lib.inner_adapt(lenet_loss, m, (i, l), alpha),
        (models, images, labels), microbatch=microbatch,
        client_shards=client_shards)


# --------------------------------------------------------------------------


def run_fl(cfg: FLRunConfig, verbose: bool = False) -> Dict[str, list]:
    """Run a full FL experiment; history dict with entries at every
    ``eval_every``-th round (plus the last) and the re-cluster count.

    Compatibility wrapper: execution happens in the scan-compiled engine
    (`repro.core.engine`), one XLA program for the whole run."""
    from repro.core import engine   # late import: engine imports this module
    return engine.run(cfg, verbose=verbose)


def run_fl_legacy(cfg: FLRunConfig, verbose: bool = False) -> Dict[str, list]:
    """The original host-side round loop (one device sync per round).

    Kept as the reference implementation: `tests/test_engine_parity.py`
    asserts the scan engine reproduces this trajectory for the five
    always-up paper methods (the connectivity-gated strategies are
    engine-only — they have no legacy loop)."""
    assert cfg.method in strat_lib.PAPER_METHODS, cfg.method
    rng = jax.random.PRNGKey(cfg.seed)
    r_data, r_part, r_model, r_freq, r_kmeans, r_loop = jax.random.split(rng, 6)

    # ---- data ------------------------------------------------------------
    n_total = cfg.num_clients * cfg.samples_per_client
    (images, labels), (test_x, test_y) = make_split(
        r_data, cfg.dataset, n_total, cfg.eval_size)
    client_idx = dirichlet_partition(r_part, labels, cfg.num_clients,
                                     cfg.dirichlet_alpha,
                                     cfg.samples_per_client,
                                     num_classes=cfg.dataset.num_classes)
    data_sizes = jnp.full((cfg.num_clients,), cfg.samples_per_client,
                          jnp.float32)

    # ---- models ----------------------------------------------------------
    w0 = init_lenet(r_model, cfg.dataset.channels, cfg.dataset.img,
                    cfg.dataset.num_classes)
    params_stack = agg.broadcast_global(w0, cfg.num_clients)
    model_bits = sum(x.size for x in jax.tree_util.tree_leaves(w0)) * 32.0
    sample_bits = cfg.dataset.img ** 2 * cfg.dataset.channels * 32.0

    # ---- orbital setup -----------------------------------------------------
    planes = int(math.sqrt(cfg.num_clients))
    while cfg.num_clients % planes:
        planes -= 1
    constellation = Constellation(num_planes=planes,
                                  sats_per_plane=cfg.num_clients // planes)
    gs0 = ground_station_position(t_s=0.0)
    lp, cp = LinkParams(), cost_lib.ComputeParams()
    freqs = cost_lib.sample_freqs(r_freq, cfg.num_clients, cp)

    # ---- clustering -------------------------------------------------------
    k = 1 if cfg.method == "c-fedavg" else cfg.num_clusters
    pos0 = constellation.positions(0.0)
    if cfg.method in ("fedhc", "fedhc-nomaml"):
        res = cl.kmeans(pos0, k, r_kmeans)
        assignment, centroids = res.assignment, res.centroids
    elif cfg.method == "fedce":
        # cluster on label-distribution space (client class histograms)
        hists = jax.vmap(lambda idx: jnp.bincount(
            labels[idx], length=cfg.dataset.num_classes))(client_idx)
        hists = hists / cfg.samples_per_client
        res = cl.kmeans(hists.astype(jnp.float32), k, r_kmeans)
        assignment = res.assignment
        centroids = cl.update_centroids(pos0, assignment,
                                        pos0[res.ps_index])
    elif cfg.method == "h-base":
        assignment = jax.random.randint(r_kmeans, (cfg.num_clients,), 0, k
                                        ).astype(jnp.int32)
        centroids = cl.update_centroids(pos0, assignment, pos0[:k])
    else:  # c-fedavg
        assignment = jnp.zeros((cfg.num_clients,), jnp.int32)
        centroids = pos0.mean(0, keepdims=True)

    def ps_of(positions, centroids, assignment):
        d = cl.pairwise_sq_dist(positions, centroids)
        same = jax.nn.one_hot(assignment, k, dtype=bool).T
        return jnp.argmin(jnp.where(same, d.T, jnp.inf), axis=1).astype(jnp.int32)

    ps_index = ps_of(pos0, centroids, assignment)

    # ---- jitted round pieces ----------------------------------------------
    local_train = jax.jit(functools.partial(_local_train, lr=cfg.lr,
                                            steps=cfg.local_steps))
    eval_acc = jax.jit(lenet_accuracy)
    hier_round = jax.jit(functools.partial(
        agg.hierarchical_round, k=k,
        loss_weighted=cfg.method in ("fedhc", "fedhc-nomaml")),
        static_argnames=("do_global",))
    meta_update = jax.jit(functools.partial(
        _meta_update_clusters, k=k, alpha=cfg.maml_alpha, beta=cfg.maml_beta))
    member_adapt = jax.jit(functools.partial(_adapt_members,
                                             alpha=cfg.maml_alpha))
    cluster_costs = jax.jit(functools.partial(
        cost_lib.cluster_round_costs, model_bits=model_bits, lp=lp, cp=cp))
    ground_costs = jax.jit(functools.partial(
        cost_lib.ground_round_costs, model_bits=model_bits, lp=lp))
    cfedavg_costs = jax.jit(functools.partial(
        cost_lib.cfedavg_round_costs, sample_bits=sample_bits,
        server_freq_hz=cp.max_freq_hz, lp=lp, cp=cp))

    history = {"round": [], "acc": [], "loss": [], "time_s": [],
               "energy_j": [], "reclusters": 0}
    t_sim, e_sim = 0.0, 0.0
    centralized = w0 if cfg.method == "c-fedavg" else None

    rows = sample_rows(images)
    for rnd in range(cfg.rounds):
        r_rnd = jax.random.fold_in(r_loop, rnd)
        positions = constellation.positions(t_sim)
        gs = ground_station_position(t_s=t_sim)
        do_global = (rnd + 1) % cfg.rounds_per_global == 0

        imgs, labs = client_batches(rows, labels, client_idx, r_rnd,
                                    cfg.batch_size, images.shape[1:])

        if cfg.method == "c-fedavg":
            # centralized: the server performs all clients' steps serially
            for s in range(cfg.local_steps):
                b = jax.random.fold_in(r_rnd, s)
                picks = jax.random.randint(b, (cfg.batch_size,), 0, n_total)
                l, g = jax.value_and_grad(lenet_loss)(
                    centralized, (images[picks], labels[picks]))
                centralized = jax.tree_util.tree_map(
                    lambda a, gg: a - cfg.lr * gg, centralized, g)
            if cfg.local_steps == 0:
                # no training this round: report the current model's loss
                picks = jax.random.randint(jax.random.fold_in(r_rnd, 0),
                                           (cfg.batch_size,), 0, n_total)
                l = lenet_loss(centralized, (images[picks], labels[picks]))
            participating = jnp.ones((cfg.num_clients,), bool)
            server_pos = positions[int(ps_index[0])]
            t_r, e_r = cfedavg_costs(positions, server_pos, participating,
                                     data_sizes, freqs)
            # server does C*local_steps minibatches, clients none
            loss_val = float(l)
        else:
            # Every satellite trains every round.  Geometry drift shows up
            # as (a) longer links to the (stale) cluster PS — more time and
            # energy — and (b) the dropout-rate trigger: a satellite whose
            # nearest centroid changed has "left" its cluster (Alg. 1).
            nearest = cl.assign(positions, centroids)
            in_region = nearest == assignment
            participating = jnp.ones_like(in_region)

            params_stack, losses = local_train(params_stack, imgs, labs)
            params_stack = hier_round(params_stack, losses, data_sizes,
                                      assignment,
                                      participating=participating,
                                      do_global=bool(do_global))
            loss_val = float(jnp.mean(losses))

            ps_positions = positions[ps_index][assignment]
            t_r, e_r = cluster_costs(positions, ps_positions, assignment,
                                     participating, data_sizes, freqs)
            if do_global:
                t_g, e_g = ground_costs(positions[ps_index], gs)
                t_r, e_r = t_r + t_g, e_r + e_g

            # ---- re-cluster check (Alg. 1 lines 14-18) -------------------
            if cfg.method in ("fedhc", "fedhc-nomaml") and do_global:
                d_r = cl.dropout_rate(in_region, assignment, k)
                if float(jnp.max(d_r)) > cfg.dropout_threshold:
                    history["reclusters"] += 1
                    res = cl.kmeans(positions, k,
                                    jax.random.fold_in(r_kmeans, rnd))
                    new_assignment, centroids = res.assignment, res.centroids
                    ps_index = res.ps_index
                    cluster_models = agg.cluster_aggregate(
                        params_stack,
                        agg.loss_weights(losses, new_assignment, k),
                        new_assignment, k)
                    if cfg.method == "fedhc":
                        cluster_models = meta_update(
                            cluster_models, new_assignment, imgs, labs)
                    changed = new_assignment != assignment
                    inherited = agg.broadcast_clusters(cluster_models,
                                                       new_assignment)
                    if cfg.method == "fedhc":
                        # each joining member takes MAML inner steps on its
                        # own data from the meta-updated cluster model
                        inherited = member_adapt(inherited, imgs, labs)
                    params_stack = jax.tree_util.tree_map(
                        lambda inh, old: jnp.where(
                            changed.reshape((-1,) + (1,) * (inh.ndim - 1)),
                            inh, old), inherited, params_stack)
                    assignment = new_assignment

        t_sim += float(t_r) + cfg.round_minutes * 60.0
        e_sim += float(e_r)

        if (rnd + 1) % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
            if cfg.method == "c-fedavg":
                global_model = centralized
            else:
                global_model = jax.tree_util.tree_map(
                    lambda x: jnp.mean(x.astype(jnp.float32), 0), params_stack)
            acc = float(eval_acc(global_model, test_x, test_y))
            history["round"].append(rnd + 1)
            history["acc"].append(acc)
            history["loss"].append(loss_val)
            history["time_s"].append(t_sim)
            history["energy_j"].append(e_sim)
            if verbose:
                print(f"[{cfg.method} K={k}] round {rnd+1:4d} "
                      f"acc={acc:.3f} loss={loss_val:.3f} "
                      f"T={t_sim:.0f}s E={e_sim:.1f}J")
    return history


def time_energy_to_accuracy(history: Dict[str, list], target: float):
    """First (time, energy) at which accuracy >= target, else (inf, inf).

    Legacy helper over the history-dict format; the typed equivalent is
    ``RunResult.time_to_accuracy(target)`` (`repro.api`), which returns
    ``None`` when the target is never reached."""
    for r, a, t, e in zip(history["round"], history["acc"],
                          history["time_s"], history["energy_j"]):
        if a >= target:
            return t, e, r
    return float("inf"), float("inf"), -1
