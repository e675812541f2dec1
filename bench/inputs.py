"""The benchmark's inputs, made from ``--seed`` alone.

One jitted call on the device makes the data (class-template images with
noise, MNIST or CIFAR geometry), the non-IID client split (each client's
label mixture drawn from Dirichlet(alpha), its samples its own), the
clients' CPU frequencies, the initial LeNet weights and the two loop
keys.  The initial clustering (k-means over the t=0 positions, the PS of
each cluster its member nearest the centroid) is made on the host in
float64.  The same seed gives the same arrays on every run.

`to_program` lays them out as the program's ``(state0, data)`` with the
program's own shardings (the state as the method's reference file,
``reference/<method>.py``, lays it out), so that ``repro.api.run``
(given them through its caller-owned ``setup_cache``) runs on exactly
these inputs, and the reference takes the same dict.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import common as ref


def seed_key(seed: int):
    """A key from a seed of up to 64 bits (PRNGKey keeps only 32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def _smooth(key, shape, img):
    coarse = jax.random.normal(key, shape[:-3] + (7, 7, shape[-1]))
    return jax.image.resize(coarse, shape[:-3] + (img, img, shape[-1]),
                            method="bilinear")


@functools.partial(jax.jit, static_argnames=("n", "spc", "img", "ch",
                                             "classes", "eval_size",
                                             "alpha", "template_scale",
                                             "noise_scale"))
def _make(key, *, n, spc, img, ch, classes, eval_size, alpha,
          template_scale, noise_scale):
    ks = jax.random.split(key, 12)
    templates = _smooth(ks[0], (classes, img, img, ch), img) * template_scale
    mix = jax.random.dirichlet(ks[1], jnp.full((classes,), alpha), (n,))
    labels = jax.random.categorical(
        ks[2], jnp.log(mix)[:, None, :], shape=(n, spc)).reshape(-1)
    images = (templates[labels]
              + jax.random.normal(ks[3], (n * spc, img, img, ch))
              * noise_scale)
    test_y = jax.random.randint(ks[4], (eval_size,), 0, classes)
    test_x = (templates[test_y]
              + jax.random.normal(ks[5], (eval_size, img, img, ch))
              * noise_scale)
    s1 = (img - 4) // 2
    s2 = (s1 - 4) // 2

    def dense(key, shape, fan):
        return jax.random.normal(key, shape) / math.sqrt(fan)

    params = {
        "c1": {"w": dense(ks[6], (5, 5, ch, 6), 25 * ch),
               "b": jnp.zeros((6,))},
        "c2": {"w": dense(ks[7], (5, 5, 6, 16), 150),
               "b": jnp.zeros((16,))},
        "f1": {"w": dense(ks[8], (16 * s2 * s2, 120), 16 * s2 * s2),
               "b": jnp.zeros((120,))},
        "f2": {"w": dense(ks[9], (120, 84), 120), "b": jnp.zeros((84,))},
        "f3": {"w": dense(ks[10], (84, classes), 84),
               "b": jnp.zeros((classes,))},
    }
    kf, k_rng, k_km = jax.random.split(ks[11], 3)
    freqs = jax.random.uniform(kf, (n,), minval=1.0e8, maxval=1.0e9)
    return {
        "images": images.astype(jnp.float32),
        "labels": labels.astype(jnp.int32),
        "test_x": test_x.astype(jnp.float32),
        "test_y": test_y.astype(jnp.int32),
        "client_idx": jnp.arange(n * spc, dtype=jnp.int32).reshape(n, spc),
        "freqs": freqs.astype(jnp.float32),
        "params": params,
        "rng": k_rng,
        "r_kmeans": k_km,
    }


def initial_clusters(spec: ref.Spec, seed: int, iters: int = 100):
    """k-means (float64, host) over the t=0 positions from k distinct
    satellites drawn from the seed: (assignment, centroids, ps_index)."""
    x = np.asarray(ref.positions(spec, jnp.float32(0.0)), np.float64)
    rs = np.random.default_rng(seed)
    c = x[rs.choice(x.shape[0], spec.k, replace=False)]
    for _ in range(iters):
        d = ((x[:, None, :] - c[None]) ** 2).sum(-1)
        a = d.argmin(1)
        new = np.stack([x[a == j].mean(0) if (a == j).any() else c[j]
                        for j in range(spec.k)])
        if np.allclose(new, c):
            break
        c = new
    d = ((x[:, None, :] - c[None]) ** 2).sum(-1)
    a = d.argmin(1)
    ps = np.array([np.where(a == j, d[:, j], np.inf).argmin()
                   for j in range(spec.k)])
    return (jnp.asarray(a, jnp.int32), jnp.asarray(c, jnp.float32),
            jnp.asarray(ps, jnp.int32))


def make(spec: ref.Spec, data_cfg: dict, seed: int) -> dict:
    """All inputs of one run, on the default device."""
    inp = _make(seed_key(seed), n=spec.n, spc=spec.spc, img=spec.img,
                ch=spec.ch, classes=spec.classes,
                eval_size=data_cfg["eval_size"],
                alpha=float(data_cfg["dirichlet_alpha"]),
                template_scale=float(data_cfg["dataset"]["template_scale"]),
                noise_scale=float(data_cfg["dataset"]["noise_scale"]))
    a, c, ps = initial_clusters(spec, seed)
    inp.update(assignment=a, centroids=c, ps_index=ps)
    return inp


def to_program(spec: ref.Spec, inp: dict, entry, method):
    """``(state0, data)`` in the program's own types and placement: each
    array goes where the program's setup put the matching one.
    ``method`` is the method's reference module, which lays out the
    state (``program_state``)."""
    state0, data = entry
    n = spec.n
    new_data = data._replace(
        images=inp["images"], labels=inp["labels"], test_x=inp["test_x"],
        test_y=inp["test_y"], client_idx=inp["client_idx"],
        data_sizes=jnp.full((n,), float(spec.spc), jnp.float32),
        freqs=inp["freqs"], r_kmeans=inp["r_kmeans"])
    new_state = method.program_state(spec, inp, state0)

    def place(new, old):
        new = jnp.asarray(new)
        if new.shape != old.shape or new.dtype != old.dtype:
            raise ValueError(f"input {new.shape} {new.dtype} does not match "
                             f"the program's {old.shape} {old.dtype}")
        return jax.device_put(new, old.sharding)

    return (jax.tree_util.tree_map(place, new_state, state0),
            jax.tree_util.tree_map(place, new_data, data))
