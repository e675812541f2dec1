"""Shared pieces of the plain FedHC references (`fedhc.py`,
`fedhc-async.py` beside this file).

Written from the method's description (paper arXiv 2502.12783, Alg. 1 and
§II-C, §III-B, §III-C) in straightforward ``jax.numpy``; nothing here or
in the method files imports the program under test, and they take only
the inputs the benchmark made from the seed (`inputs.py`): the Walker
geometry, the link and energy cost model (Eq. 7-9), k-means (Eq. 13-15),
LeNet-5, the stage-1 and stage-2 aggregations (Eq. 12) and the MAML task
gradient (Eq. 16).

``precision`` names how the model math is computed:

* ``"stated"``: as the configuration states — the LeNet convolutions and
  dense layers at the chip's default f32 matmul precision, positions,
  distances and the aggregation sums at full f32 (``HIGHEST``);
* ``"bf16"``: the control one step below — parameters, data,
  activations, gradients and aggregates held in bfloat16.

``fault`` plants one of the faults the correctness check must catch
(``"unchanged"``: local training returns the model unchanged;
``"half_batch"``: the loss and gradient are means over half of each
minibatch; ``"answer"``: the accuracy is computed against shifted
labels; ``"cost"``: the members' uploads are costed at half the model's
bits).  Per-client work runs in blocks of ``block`` clients.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
R_EARTH_KM = 6371.0
MU_KM3_S2 = 398600.4418
OMEGA_EARTH = 7.2921159e-5


@dataclass(frozen=True)
class Spec:
    """Everything the reference needs, read from the cell's files."""
    n: int                      # clients (satellites)
    k: int                      # clusters
    planes: int
    sats_per_plane: int
    img: int
    ch: int
    classes: int
    spc: int                    # samples per client
    batch: int
    steps: int                  # local SGD steps per round
    lr: float
    m: int                      # rounds (flushes) per stage-2
    z: float                    # dropout threshold
    alpha: float                # MAML inner rate
    beta: float                 # MAML outer rate
    round_minutes: float
    rounds: int                 # rounds (sync) or events (async)
    altitude_km: float = 1300.0
    inclination_deg: float = 53.0
    phasing: float = 1.0
    gs_lat_deg: float = 30.0
    gs_lon_deg: float = 114.0
    bandwidth_hz: float = 1.0e6
    tx_power_w: float = 0.5
    noise_w: float = 1.0e-10
    gain_km2: float = 4.0e-4
    gs_gain_boost: float = 4.0
    cycles_per_sample: float = 2.0e6
    eps0: float = 1.0e-10
    cohort: int = 0
    buffer: int = 0
    staleness_a: float = 0.5
    block: int = 100


# ---------------------------------------------------------------- geometry
def positions(spec: Spec, t):
    """Walker-delta ECI positions (km) at time t: sat = plane*S + slot."""
    p_, s_ = spec.planes, spec.sats_per_plane
    r = R_EARTH_KM + spec.altitude_km
    period = 2.0 * math.pi * math.sqrt(r ** 3 / MU_KM3_S2)
    inc = math.radians(spec.inclination_deg)
    plane = jnp.arange(p_)
    raan = 2.0 * math.pi * plane / p_
    anom = ((2.0 * math.pi * jnp.arange(s_) / s_)[None, :]
            + (2.0 * math.pi * spec.phasing * plane / (p_ * s_))[:, None])
    u = anom + 2.0 * math.pi * t / period
    cu, su = jnp.cos(u), jnp.sin(u)
    co, so = jnp.cos(raan)[:, None], jnp.sin(raan)[:, None]
    x = cu * co - su * so * math.cos(inc)
    y = cu * so + su * co * math.cos(inc)
    z = su * math.sin(inc)
    return (jnp.stack([x, y, z], -1) * r).reshape(p_ * s_, 3)


def ground_station(spec: Spec, t):
    lat, lon = math.radians(spec.gs_lat_deg), math.radians(spec.gs_lon_deg)
    lon = lon + OMEGA_EARTH * t
    return R_EARTH_KM * jnp.stack([math.cos(lat) * jnp.cos(lon),
                                   math.cos(lat) * jnp.sin(lon),
                                   jnp.full_like(lon, math.sin(lat))])


def sq_dist(x, c):
    """Eq. 13, squared: |x|^2 - 2 x.c + |c|^2, (N, K)."""
    return (jnp.sum(x * x, -1)[:, None]
            - 2.0 * jnp.matmul(x, c.T, precision=HI)
            + jnp.sum(c * c, -1)[None, :])


def nearest(x, c):
    return jnp.argmin(sq_dist(x, c), axis=1).astype(jnp.int32)


def kmeans(x, k, key, iters=32, tol=1e-4):
    """Lloyd's k-means from k distinct random satellites; stops moving a
    centroid set once its shift falls under ``tol`` (Eq. 15).  Returns
    (assignment, centroids, ps): the PS of a cluster is its member
    nearest the centroid."""
    c = x[jax.random.choice(key, x.shape[0], (k,), replace=False)]
    done = False
    for _ in range(iters):
        a = nearest(x, c)
        oh = jax.nn.one_hot(a, k, dtype=x.dtype)
        cnt = oh.sum(0)
        new = jnp.where(cnt[:, None] > 0,
                        jnp.matmul(oh.T, x, precision=HI)
                        / jnp.maximum(cnt, 1.0)[:, None], c)
        shift = jnp.sum(jnp.square(new - c))
        if not done:
            c = new
        done = done or bool(shift < tol)
    a = nearest(x, c)
    d = jnp.where(jax.nn.one_hot(a, k, dtype=bool).T, sq_dist(x, c).T,
                  jnp.inf)
    return a, c, jnp.argmin(d, axis=1).astype(jnp.int32)


# ------------------------------------------------------------------- costs
def rate(spec: Spec, d_km, ground=False):
    g = spec.gain_km2 / jnp.maximum(d_km, 1.0) ** 2
    if ground:
        g = g * spec.gs_gain_boost
    return spec.bandwidth_hz * jnp.log(1.0 + spec.tx_power_w * g
                                       / spec.noise_w)


def member_costs(spec: Spec, pos, ps_pos, freqs, model_bits):
    """Per member: compute + upload time, and 2 transmissions + compute
    energy (Eq. 7-9)."""
    d = jnp.linalg.norm(pos - ps_pos, axis=-1)
    t_cmp = spec.spc * spec.cycles_per_sample / freqs
    t_com = model_bits / jnp.maximum(rate(spec, d), 1.0)
    e = (2.0 * spec.tx_power_w * t_com
         + spec.eps0 * freqs * t_cmp)
    return t_cmp + t_com, e


def ground_costs(spec: Spec, ps_pos, gs, model_bits):
    d = jnp.linalg.norm(ps_pos - gs[None, :], axis=-1)
    t = model_bits / jnp.maximum(rate(spec, d, ground=True), 1.0)
    return jnp.max(t), jnp.sum(2.0 * spec.tx_power_w * t)


# ------------------------------------------------------------------- model
def dtype_of(precision):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def forward(p, x):
    """LeNet-5: conv5-relu-pool2, conv5-relu-pool2, 120-84-classes."""
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, q):
        y = jax.lax.conv_general_dilated(x, q["w"], (1, 1), "VALID",
                                         dimension_numbers=dn)
        return jax.nn.relu(y + q["b"])

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(conv(x, p["c1"]))
    x = pool(conv(x, p["c2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"]["w"] + p["f1"]["b"])
    x = jax.nn.relu(x @ p["f2"]["w"] + p["f2"]["b"])
    return x @ p["f3"]["w"] + p["f3"]["b"]


def xent(p, x, y):
    logits = forward(p, x).astype(jnp.float32)
    ll = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


def sgd(p, g, rate):
    return jax.tree_util.tree_map(lambda a, b: (a - rate * b).astype(a.dtype),
                                  p, g)


def local_steps(p, x, y, *, lr, steps, fault):
    """``steps`` SGD steps; returns (model, loss at the last step)."""
    if fault == "half_batch":
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    q, loss = p, jnp.float32(0.0)
    for _ in range(steps):
        loss, g = jax.value_and_grad(xent)(q, x, y)
        q = sgd(q, g, lr)
    if fault == "unchanged":
        q = p
    return q, loss


def task_grad(model, x, y, alpha):
    """MAML task gradient as the simulator takes it: the loss gradient at
    the model after one inner step (Eq. 16), not differentiated through
    that step (first-order MAML)."""
    adapted = sgd(model, jax.grad(xent)(model, x, y), alpha)
    return jax.grad(xent)(adapted, x, y)


def blocks(fn, block, *args):
    """``vmap(fn)`` over the leading client dim, ``block`` clients at a
    time, concatenated."""
    n = jax.tree_util.tree_leaves(args)[0].shape[0]
    outs = [fn(*jax.tree_util.tree_map(lambda a: a[i:i + block], args))
            for i in range(0, n, block)]
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *outs)


def seg(oh, x):
    """Per-cluster sums over the client dim at full f32: (K, ...)."""
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jnp.matmul(oh.T, flat, precision=HI).reshape(
        (oh.shape[1],) + x.shape[1:])


def cluster_avg(stack, w, a, k, dtype):
    """Stage 1: per-cluster weighted sum with weights w (normalized per
    cluster)."""
    wm = jax.nn.one_hot(a, k, dtype=jnp.float32) * w[:, None]
    return jax.tree_util.tree_map(lambda x: seg(wm, x).astype(dtype), stack)


def inv_loss_weights(losses, a, k, mask=None):
    """Eq. 12: (1/L_i) over the cluster's sum of 1/L_j."""
    inv = 1.0 / jnp.maximum(losses, 1e-8)
    if mask is not None:
        inv = inv * mask
    oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
    den = jnp.matmul(oh.T, inv, precision=HI)
    return inv / jnp.maximum(den[a], 1e-12)


def global_avg(cluster_models, a, k, spc):
    """Stage 2: clusters weighted by data size D_k / D."""
    dk = jnp.sum(jax.nn.one_hot(a, k, dtype=jnp.float32), 0) * float(spc)
    w = dk / jnp.maximum(jnp.sum(dk), 1e-12)
    return jax.tree_util.tree_map(
        lambda x: jnp.sum(x.astype(jnp.float32)
                          * w.reshape((-1,) + (1,) * (x.ndim - 1)),
                          0).astype(x.dtype), cluster_models)


def accuracy(p, x, y, fault):
    if fault == "answer":
        y = (y + 1) % 10
    pred = jnp.argmax(forward(p, x).astype(jnp.float32), -1)
    return jnp.mean((pred == y).astype(jnp.float32))


def client_mean(stack):
    return jax.tree_util.tree_map(
        lambda x: jnp.mean(x.astype(jnp.float32), 0), stack)


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def model_bits(params0) -> float:
    return float(sum(np.prod(x.shape) for x in
                     jax.tree_util.tree_leaves(params0))) * 32.0


def batches(key, client_idx, images, labels, batch, n):
    picks = jax.random.randint(key, (n, batch), 0, client_idx.shape[1])
    flat = jnp.take_along_axis(client_idx, picks, axis=1)
    return images[flat], labels[flat]


def stack(tree, rows):
    """``rows`` copies of every leaf along a new leading dim."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (rows,) + x.shape), tree)
