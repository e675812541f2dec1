"""LeNet's convolution (`models.lenet._conv`) against an independent
im2col reference written here: per-client loss and parameter gradients
under ``jax.vmap`` over per-client weights, as the FL hot loop runs them,
in MNIST (28x28x1) and CIFAR (32x32x3) geometry.

Both sides run at ``HIGHEST`` precision, so they differ only in the order
in which float32 sums are taken (a 5x5xC window's products, the batch's
gradient terms).  On the CPU that reordering moves the losses (2.2 to
2.7) by at most 2.4e-7 and any gradient entry (at most 0.63) by at most
2e-7, a couple of ulps of the sums.  The tolerance (rtol 1e-5, atol 1e-6)
leaves five times that room and stays far under a real fault: the first
layer's kernel flipped left to right moves every client's loss by 0.009
or more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lenet import init_lenet, lenet_forward, lenet_loss

CLIENTS, BATCH = 8, 16
RTOL, ATOL = 1e-5, 1e-6


def _conv_im2col(x, w, b):
    """VALID conv: 25 shifted slices side by side on the channel axis,
    then one matmul with the (kh*kw*cin, cout) kernel."""
    kh, kw, cin, cout = w.shape
    H, W = x.shape[1], x.shape[2]
    cols = [x[:, i:H - kh + 1 + i, j:W - kw + 1 + j, :]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1) @ w.reshape(-1, cout) + b


def _pool(x):
    B, H, W, C = x.shape
    return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))


def _ref_loss(p, images, labels):
    x = _pool(jax.nn.relu(_conv_im2col(images, p["c1"]["w"], p["c1"]["b"])))
    x = _pool(jax.nn.relu(_conv_im2col(x, p["c2"]["w"], p["c2"]["b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"]["w"] + p["f1"]["b"])
    x = jax.nn.relu(x @ p["f2"]["w"] + p["f2"]["b"])
    logits = x @ p["f3"]["w"] + p["f3"]["b"]
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def _clients(img, ch, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), CLIENTS + 3)
    params = jax.vmap(lambda r: init_lenet(r, ch, img))(k[:CLIENTS])
    # non-zero biases, so a dropped or misplaced bias shows
    for layer in params.values():
        layer["b"] = 0.1 * jax.random.normal(k[CLIENTS], layer["b"].shape)
    images = jax.random.normal(k[CLIENTS + 1], (CLIENTS, BATCH, img, img, ch))
    labels = jax.random.randint(k[CLIENTS + 2], (CLIENTS, BATCH), 0, 10)
    return params, images, labels


@pytest.mark.parametrize("img, ch", [(28, 1), (32, 3)],
                         ids=["mnist", "cifar"])
def test_vmapped_loss_and_grads_match_im2col(img, ch):
    params, images, labels = _clients(img, ch)
    with jax.default_matmul_precision("highest"):
        got_l, got_g = jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, x, y: lenet_loss(p, (x, y)))))(params, images, labels)
        ref_l, ref_g = jax.jit(jax.vmap(jax.value_and_grad(_ref_loss)))(
            params, images, labels)
    np.testing.assert_allclose(got_l, ref_l, rtol=RTOL, atol=ATOL)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(g, r, rtol=RTOL, atol=ATOL,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("img, ch", [(28, 1), (32, 3)],
                         ids=["mnist", "cifar"])
def test_vmapped_forward_is_one_grouped_conv_per_layer(img, ch):
    """Under vmap over per-client weights each conv layer is one
    convolution with the clients as feature groups, and no patches
    tensor (no concatenate) is built."""
    params, images, _ = _clients(img, ch)
    jaxpr = jax.make_jaxpr(jax.vmap(lenet_forward))(params, images)
    prims = [e.primitive.name for e in jaxpr.eqns]
    groups = [e.params["feature_group_count"] for e in jaxpr.eqns
              if e.primitive.name == "conv_general_dilated"]
    assert groups == [CLIENTS] * 2
    assert "concatenate" not in prims
