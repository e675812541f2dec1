"""Plain reference of synchronous FedHC (method ``fedhc``).

One round: every client takes ``steps`` SGD steps on a fresh minibatch;
loss-weighted stage-1 aggregation per cluster; every ``m``-th round the
stage-2 (ground station) aggregation weighted by cluster data size, and
when a cluster's dropout rate exceeds ``Z`` a re-cluster: k-means on
positions, MAML meta-update of the cluster models (Eq. 16-17) and one
inner step for members that changed cluster.  ``run`` returns the
per-round history: training loss, test accuracy, cumulative simulated
time and energy, and the re-cluster and stage-2 counts.

``program_state`` lays the same inputs out as the sync engine's
``RoundState``, so that the program starts from them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from reference.common import (Spec, accuracy, batches, blocks, cast,
                              client_mean, cluster_avg, dtype_of, global_avg,
                              ground_costs, ground_station, inv_loss_weights,
                              kmeans, local_steps, member_costs, model_bits,
                              nearest, positions, seg, sgd, stack, task_grad,
                              xent)


def program_state(spec: Spec, inp: Dict, state0):
    """The sync engine's initial ``RoundState`` holding these inputs."""
    return state0._replace(
        params=stack(inp["params"], spec.n), assignment=inp["assignment"],
        centroids=inp["centroids"], ps_index=inp["ps_index"],
        rng=inp["rng"], t_sim=jnp.float32(0.0), e_sim=jnp.float32(0.0),
        reclusters=jnp.int32(0), pending_global=jnp.bool_(False))


def run(spec: Spec, inp: Dict, precision: str = "stated",
        fault: Optional[str] = None) -> Dict:
    """The synchronous FedHC run; returns the per-round history."""
    dt = dtype_of(precision)
    n, k = spec.n, spec.k
    images = inp["images"].astype(dt)
    test_x = inp["test_x"].astype(dt)
    bits = model_bits(inp["params"])
    member_bits = bits / 2 if fault == "cost" else bits
    params = cast(stack(inp["params"], n), dt)
    a, cen, ps = inp["assignment"], inp["centroids"], inp["ps_index"]
    t_sim, e_sim = jnp.float32(0.0), jnp.float32(0.0)
    freqs = inp["freqs"]

    train = jax.jit(jax.vmap(lambda p, x, y: local_steps(
        p, x, y, lr=spec.lr, steps=spec.steps, fault=fault)))
    tgrad = jax.jit(jax.vmap(lambda m, x, y: task_grad(m, x, y,
                                                        spec.alpha)))
    adapt = jax.jit(jax.vmap(lambda m, x, y: sgd(
        m, jax.grad(xent)(m, x, y), spec.alpha)))
    acc_fn = jax.jit(lambda p, x, y: accuracy(p, x, y, fault))
    hist = {"loss": [], "acc": [], "time_s": [], "energy_j": [],
            "reclusters": 0, "global_rounds": 0}
    for rnd in range(spec.rounds):
        key = jax.random.fold_in(inp["rng"], rnd)
        pos = positions(spec, t_sim)
        do_global = (rnd + 1) % spec.m == 0
        x, y = batches(key, inp["client_idx"], images, inp["labels"],
                        spec.batch, n)
        in_region = nearest(pos, cen) == a

        params, losses = blocks(train, spec.block, params, x, y)
        losses = losses.astype(jnp.float32)
        cm = cluster_avg(params, inv_loss_weights(losses, a, k), a, k, dt)
        if do_global:
            g = global_avg(cm, a, k, spec.spc)
            params = jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), g)
        else:
            params = jax.tree_util.tree_map(lambda v: v[a], cm)

        t_i, e_i = member_costs(spec, pos, pos[ps][a], freqs, member_bits)
        t_r, e_r = jnp.max(t_i), jnp.sum(e_i)
        if do_global:
            t_g, e_g = ground_costs(spec, pos[ps], ground_station(spec,
                                                                  t_sim),
                                    bits)
            t_r, e_r = t_r + t_g, e_r + e_g

        oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
        drop = (jnp.sum(oh * (~in_region).astype(jnp.float32)[:, None], 0)
                / jnp.maximum(oh.sum(0), 1.0))
        if do_global and float(jnp.max(drop)) > spec.z:
            a2, cen2, ps2 = kmeans(pos, k,
                                   jax.random.fold_in(inp["r_kmeans"], rnd))
            cm = cluster_avg(params, inv_loss_weights(losses, a2, k),
                              a2, k, dt)
            members = jax.tree_util.tree_map(lambda v: v[a2], cm)
            grads = blocks(tgrad, spec.block, members, x, y)
            oh2 = jax.nn.one_hot(a2, k, dtype=jnp.float32)
            cm = jax.tree_util.tree_map(
                lambda c, g: (c - spec.beta * seg(oh2, g)).astype(dt),
                cm, grads)
            inherited = blocks(adapt, spec.block,
                                jax.tree_util.tree_map(lambda v: v[a2], cm),
                                x, y)
            moved = a2 != a
            params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    moved.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                inherited, params)
            a, cen, ps = a2, cen2, ps2
            hist["reclusters"] += 1
        hist["global_rounds"] += int(do_global)

        t_sim = t_sim + t_r + spec.round_minutes * 60.0
        e_sim = e_sim + e_r
        hist["loss"].append(float(jnp.mean(losses)))
        hist["acc"].append(float(acc_fn(cast(client_mean(params), dt), test_x,
                                        inp["test_y"])))
        hist["time_s"].append(float(t_sim))
        hist["energy_j"].append(float(e_sim))
    return hist
