"""LeNet-5-style CNN — the model the FedHC paper actually trains (§IV-A:
"employing the LeNet model", batch 64, SGD lr 0.01, MNIST / CIFAR-10).

Pure-functional JAX; used by the FL experiments and benchmarks.  Supports
1-channel 28x28 (MNIST geometry) and 3-channel 32x32 (CIFAR geometry).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def init_lenet(rng, in_ch: int = 1, img: int = 28, num_classes: int = 10,
               dtype=jnp.float32) -> dict:
    r = jax.random.split(rng, 5)
    # conv 5x5 valid -> pool2 -> conv 5x5 valid -> pool2 -> fc
    s1 = (img - 4) // 2                 # after conv1+pool
    s2 = (s1 - 4) // 2                  # after conv2+pool
    flat = 16 * s2 * s2

    def conv_init(rng, kh, kw, cin, cout):
        fan = kh * kw * cin
        return (jax.random.normal(rng, (kh, kw, cin, cout))
                / math.sqrt(fan)).astype(dtype)

    def fc_init(rng, cin, cout):
        return (jax.random.normal(rng, (cin, cout)) / math.sqrt(cin)).astype(dtype)

    return {
        "c1": {"w": conv_init(r[0], 5, 5, in_ch, 6), "b": jnp.zeros((6,), dtype)},
        "c2": {"w": conv_init(r[1], 5, 5, 6, 16), "b": jnp.zeros((16,), dtype)},
        "f1": {"w": fc_init(r[2], flat, 120), "b": jnp.zeros((120,), dtype)},
        "f2": {"w": fc_init(r[3], 120, 84), "b": jnp.zeros((84,), dtype)},
        "f3": {"w": fc_init(r[4], 84, num_classes),
               "b": jnp.zeros((num_classes,), dtype)},
    }


def _conv(x, p):
    """VALID conv, stride 1, as one ``lax.conv_general_dilated``
    (NHWC input, HWIO weights), plus the bias.

    The input is cast to the weight dtype and the conv runs at the
    default precision (on a TPU, bfloat16 inputs with float32
    accumulation).  Under ``vmap`` over per-client weights (the FL hot
    loop, `core.fedhc._vmap_clients`) JAX folds the block's clients into
    the feature dimension as one grouped convolution, which XLA:TPU runs
    with the clients on the lanes and no patches tensor; the weight and
    input gradients come from JAX's conv transpose rules.  On the CPU it
    is as fast or faster than im2col (16 clients' ``value_and_grad``:
    MNIST 0.077 s against 0.108 s, CIFAR 0.160 s against 0.259 s).  The
    body is scoped ``lenet_conv`` (under ``grad`` its ops read
    ``jvp(lenet_conv)`` forward and ``transpose(jvp(lenet_conv))``
    backward), so a profile names the layer."""
    with jax.named_scope("lenet_conv"):
        y = jax.lax.conv_general_dilated(
            x.astype(p["w"].dtype), p["w"], window_strides=(1, 1),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + p["b"]


def _pool(x):
    B, H, W, C = x.shape
    return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))


def lenet_forward(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """images (B, H, W, C) -> logits (B, num_classes)."""
    x = jax.nn.relu(_conv(images, params["c1"]))
    x = _pool(x)
    x = jax.nn.relu(_conv(x, params["c2"]))
    x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["f1"]["w"] + params["f1"]["b"])
    x = jax.nn.relu(x @ params["f2"]["w"] + params["f2"]["b"])
    return x @ params["f3"]["w"] + params["f3"]["b"]


def lenet_loss(params: dict, batch: Tuple[jnp.ndarray, jnp.ndarray]):
    images, labels = batch
    logits = lenet_forward(params, images)
    logz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(logz - ll)


def lenet_accuracy(params: dict, images, labels) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(lenet_forward(params, images), -1)
                     == labels).astype(jnp.float32))
