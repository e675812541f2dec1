"""Plain reference of buffered asynchronous FedHC (``fedhc-async``).

One event: the ``cohort`` clients with the earliest clocks train on the
models they fetched; their updates land in their cluster buffer with
polynomial staleness decay; full buffers flush; stage-2 after ``m``
flushes of every cluster; the cohort fetches and restarts its clocks.
``run`` returns the per-event history (plus the total of flushes).

``program_state`` lays the same inputs out as the async engine's state,
so that the program starts from them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from reference.common import (HI, Spec, accuracy, batches, blocks, cast,
                              client_mean, cluster_avg, dtype_of, global_avg,
                              ground_costs, ground_station, inv_loss_weights,
                              local_steps, member_costs, model_bits,
                              positions, stack)


def program_state(spec: Spec, inp: Dict, state0):
    """The async engine's initial state holding these inputs."""
    n, k = spec.n, spec.k
    params = stack(inp["params"], n)
    dur, e0 = initial_clocks(spec, inp)
    return state0._replace(
        work_params=params, contrib_params=params,
        cluster_params=stack(inp["params"], k),
        contrib_w=jnp.zeros((n,), jnp.float32),
        losses=jnp.ones((n,), jnp.float32),
        clock=dur, dur=dur, e_pending=e0,
        v_cluster=jnp.zeros((k,), jnp.int32),
        v_client=jnp.zeros((n,), jnp.int32),
        commits=jnp.zeros((k,), jnp.int32),
        assignment=inp["assignment"], ps_index=inp["ps_index"],
        rng=inp["rng"], t_sim=jnp.float32(0.0), e_sim=jnp.float32(0.0),
        pending_global=jnp.bool_(False))


def initial_clocks(spec: Spec, inp: Dict):
    """Each client's first round starts at t=0: (duration, energy)."""
    pos = positions(spec, jnp.float32(0.0))
    return member_costs(spec, pos, pos[inp["ps_index"]][inp["assignment"]],
                        inp["freqs"], model_bits(inp["params"]))


def run(spec: Spec, inp: Dict, precision: str = "stated",
        fault: Optional[str] = None) -> Dict:
    """The buffered asynchronous FedHC run; returns the per-event
    history (plus total flushes and the mean accepted staleness)."""
    dt = dtype_of(precision)
    n, k, cohort = spec.n, spec.k, spec.cohort
    buffer = spec.buffer or cohort
    images = inp["images"].astype(dt)
    test_x = inp["test_x"].astype(dt)
    bits = model_bits(inp["params"])
    member_bits = bits / 2 if fault == "cost" else bits
    a, ps = inp["assignment"], inp["ps_index"]
    oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
    members = oh.sum(0)
    work = contrib = cast(stack(inp["params"], n), dt)
    clusters = cast(stack(inp["params"], k), dt)
    cw = jnp.zeros((n,), jnp.float32)
    losses = jnp.ones((n,), jnp.float32)
    dur, e_pend = initial_clocks(spec, inp)
    clock = dur
    v_cl = jnp.zeros((k,), jnp.int32)
    v_me = jnp.zeros((n,), jnp.int32)
    commits = jnp.zeros((k,), jnp.int32)
    t_sim, e_sim = jnp.float32(0.0), jnp.float32(0.0)

    train = jax.jit(jax.vmap(lambda p, x, y: local_steps(
        p, x, y, lr=spec.lr, steps=spec.steps, fault=fault)))
    acc_fn = jax.jit(lambda p, x, y: accuracy(p, x, y, fault))
    hist = {"loss": [], "acc": [], "time_s": [], "energy_j": [],
            "reclusters": 0, "global_rounds": 0, "flushes": 0, "tau": []}
    for ev in range(spec.rounds):
        key = jax.random.fold_in(inp["rng"], ev)
        # 1. pop the cohort with the earliest clocks (ties: lower index)
        idx = jnp.sort(jnp.argsort(clock, stable=True)[:cohort])
        in_c = jnp.zeros((n,), bool).at[idx].set(True)
        t_event = jnp.max(clock[idx])
        # 2. train it on the models it fetched
        x, y = batches(key, inp["client_idx"], images, inp["labels"],
                        spec.batch, n)
        base = jax.tree_util.tree_map(lambda v: v[idx], work)
        trained, l_c = blocks(train, spec.block, base, x[idx], y[idx])
        losses = losses.at[idx].set(l_c.astype(jnp.float32))
        # 3. contribute with staleness decay (1 + tau)^-a
        tau = (v_cl[a] - v_me).astype(jnp.float32)
        s = (1.0 + tau) ** (-spec.staleness_a)
        cw = jnp.where(in_c, s, cw)
        contrib = jax.tree_util.tree_map(lambda o, t_: o.at[idx].set(t_),
                                         contrib, trained)
        hist["tau"].append(float(jnp.sum(jnp.where(in_c, tau, 0.0))
                                 / max(float(cohort), 1.0)))
        # 4. flush the clusters whose buffer is full
        filled = jnp.matmul(oh.T, (cw > 0).astype(jnp.float32),
                            precision=HI)
        flush = (filled >= jnp.minimum(float(buffer), members)) & (members > 0)
        w = inv_loss_weights(losses, a, k, mask=cw)
        new = cluster_avg(contrib, w, a, k, dt)
        clusters = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(
                flush.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, old),
            new, clusters)
        v_cl = v_cl + flush.astype(jnp.int32)
        commits = commits + flush.astype(jnp.int32)
        cw = jnp.where(flush[a], 0.0, cw)
        hist["flushes"] += int(jnp.sum(flush))
        # 5. stage 2 once every cluster has flushed m times
        pos = positions(spec, t_sim)
        t_g, e_g = ground_costs(spec, pos[ps], ground_station(spec, t_sim),
                                bits)
        do_g = bool(jnp.all(jnp.where(members > 0, commits >= spec.m,
                                      True)))
        if do_g:
            g = global_avg(clusters, a, k, spec.spc)
            clusters = jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(v[None], (k,) + v.shape), g)
            v_cl = v_cl + 1
            commits = jnp.zeros_like(commits)
        hist["global_rounds"] += int(do_g)
        # 6. costs and restart
        t_gs = t_g if do_g else jnp.float32(0.0)
        t_restart = jnp.maximum(t_sim, t_event + t_gs
                                + spec.round_minutes * 60.0)
        e_ev = jnp.sum(jnp.where(in_c, e_pend, 0.0))
        e_sim = e_sim + (e_ev + (e_g if do_g else 0.0))
        pos_r = positions(spec, t_restart)
        d_next, e_next = member_costs(spec, pos_r, pos_r[ps][a],
                                      inp["freqs"], member_bits)
        clock = jnp.where(in_c, t_restart + d_next, clock)
        dur = jnp.where(in_c, d_next, dur)
        e_pend = jnp.where(in_c, e_next, e_pend)
        # 7. the cohort fetches its cluster model
        work = jax.tree_util.tree_map(
            lambda c, w_: jnp.where(in_c.reshape((-1,) + (1,) * (w_.ndim - 1)),
                                    c[a], w_), clusters, work)
        v_me = jnp.where(in_c, v_cl[a], v_me)
        t_sim = t_restart
        hist["loss"].append(float(jnp.mean(losses)))
        hist["acc"].append(float(acc_fn(cast(client_mean(work), dt), test_x,
                                        inp["test_y"])))
        hist["time_s"].append(float(t_sim))
        hist["energy_j"].append(float(e_sim))
    return hist
