"""Data pipeline, optimizers, checkpointing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import given, settings, st

from repro import checkpoint as _  # noqa: F401
from repro.checkpoint.checkpoint import restore, save
from repro.data.synthetic import (CIFAR_LIKE, MNIST_LIKE, client_batches,
                                  dirichlet_partition, gather_samples,
                                  make_dataset, make_split, sample_rows)
from repro.optim import adam_init, adam_update, sgd_init, sgd_update


# ---------------------------------------------------------------- data ----

def test_dataset_shapes_and_balance():
    x, y = make_dataset(jax.random.PRNGKey(0), MNIST_LIKE, 1000)
    assert x.shape == (1000, 28, 28, 1)
    assert y.shape == (1000,)
    counts = np.bincount(np.asarray(y), minlength=10)
    assert counts.min() > 40          # roughly balanced classes


def test_split_shares_templates():
    # enough samples that the per-class means estimate the templates: at
    # 512/128 the test split has ~13 samples/class and noise (scale 1.5
    # vs template scale 0.6) swamps the estimate (corr ~0.43)
    (x, y), (tx, ty) = make_split(jax.random.PRNGKey(0), MNIST_LIKE,
                                  2048, 512)
    x, y, tx, ty = (np.asarray(a) for a in (x, y, tx, ty))
    # same class => means correlate across the split (shared templates)
    m_train = np.stack([x[y == c].mean(0).ravel() for c in range(10)])
    m_test = np.stack([tx[ty == c].mean(0).ravel() for c in range(10)])
    corr = np.corrcoef(m_train, m_test)[:10, 10:]   # (10,10) train x test
    assert corr.diagonal().min() > 0.5, corr.diagonal()
    # ...and correlate more than any *other* class's template does
    off = corr - np.diag(np.full(10, np.inf))
    assert corr.diagonal().min() > off.max(), (corr.diagonal(), off.max())


def test_dirichlet_partition_non_iid():
    x, y = make_dataset(jax.random.PRNGKey(1), MNIST_LIKE, 4000)
    idx = dirichlet_partition(jax.random.PRNGKey(2), y, 16, alpha=0.1,
                              samples_per_client=128)
    assert idx.shape == (16, 128)
    # alpha=0.1 => each client concentrated on few classes
    ent = []
    for c in range(16):
        labs = np.asarray(y)[np.asarray(idx[c])]
        p = np.bincount(labs, minlength=10) / 128
        ent.append(-(p[p > 0] * np.log(p[p > 0])).sum())
    assert np.mean(ent) < 1.8         # well below uniform ln(10)=2.3
    # labels consistent with the source dataset
    assert np.asarray(idx).max() < 4000


@pytest.mark.parametrize("picks", ["sync", "async_cohort"])
@pytest.mark.parametrize("spec", [MNIST_LIKE, CIFAR_LIKE],
                         ids=["mnist", "cifar"])
def test_client_batches_shapes(spec, picks):
    """The row gather the engines use returns, bit for bit, the 4-D
    pool's samples at the same picks: the sync round's one minibatch
    per client (``client_batches``) and the async cohort's (the cohort's
    rows of the full-width picks)."""
    x, y = make_dataset(jax.random.PRNGKey(1), spec, 512)
    idx = dirichlet_partition(jax.random.PRNGKey(2), y, 4,
                              samples_per_client=64)
    rows = sample_rows(x)
    img_shape = (spec.img, spec.img, spec.channels)
    assert rows.shape == (512, spec.img * spec.img * spec.channels)
    key = jax.random.PRNGKey(3)
    flat = jnp.take_along_axis(
        idx, jax.random.randint(key, (4, 16), 0, 64), axis=1)
    if picks == "sync":
        bx, by = client_batches(rows, y, idx, key, 16, img_shape)
    else:
        flat = flat[jnp.array([0, 2, 3])]
        bx, by = gather_samples(rows, flat, img_shape), y[flat]
    assert bx.shape == flat.shape + img_shape
    assert by.shape == flat.shape
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(x[flat]))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(y[flat]))


# -------------------------------------------------------------- optim ----

def _quad(p):
    return jnp.sum(jnp.square(p["w"] - 3.0)) + jnp.sum(jnp.square(p["b"]))


@pytest.mark.parametrize("opt", ["sgd", "sgd_momentum", "adam"])
def test_optimizers_descend_quadratic(opt):
    p = {"w": jnp.zeros((4,)), "b": jnp.ones((2,))}
    if opt == "adam":
        state = adam_init(p)
        upd = lambda p, g, s: adam_update(p, g, s, lr=0.1)
    else:
        mom = 0.9 if opt == "sgd_momentum" else 0.0
        state = sgd_init(p, momentum=mom)
        upd = lambda p, g, s: sgd_update(p, g, s, lr=0.05, momentum=mom)
    l0 = float(_quad(p))
    for _ in range(100):
        g = jax.grad(_quad)(p)
        p, state = upd(p, g, state)
    assert float(_quad(p)) < 1e-2 * l0
    assert int(state.step) == 100


@settings(max_examples=20, deadline=None)
@given(st.floats(1e-3, 0.2), st.integers(0, 1000))
def test_sgd_step_is_linear_in_grad(lr, seed):
    rng = jax.random.PRNGKey(seed)
    p = {"w": jax.random.normal(rng, (5,))}
    g = {"w": jax.random.normal(jax.random.fold_in(rng, 1), (5,))}
    new_p, _ = sgd_update(p, g, sgd_init(p), lr=lr)
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               np.asarray(p["w"] - lr * g["w"]), rtol=2e-5,
                               atol=1e-6)


# ---------------------------------------------------------- checkpoint ----

def test_checkpoint_roundtrip_structure():
    tree = {
        "layers": ({"w": jnp.arange(6.0).reshape(2, 3),
                    "b": jnp.zeros((3,), jnp.bfloat16)},
                   {"w": jnp.ones((2, 2)), "b": None}),
        "step_info": {"count": jnp.asarray(7, jnp.int32)},
    }
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        save(path, tree, step=42)
        got, step = restore(path)
    assert step == 42
    assert isinstance(got["layers"], tuple) and len(got["layers"]) == 2
    assert got["layers"][1]["b"] is None
    np.testing.assert_array_equal(got["layers"][0]["w"],
                                  np.arange(6.0).reshape(2, 3))
    assert got["layers"][0]["b"].dtype == jnp.bfloat16
    assert int(got["step_info"]["count"]) == 7
