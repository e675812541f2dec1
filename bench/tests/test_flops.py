"""``bench/flops/lenet.py`` against XLA's count for one client's
unscanned forward and backward step, where the count is exact.

XLA also counts the elementwise work (bias adds, ReLUs, pooling, the
softmax, the SGD update) that the benchmark's count leaves out, so its
count may exceed ours by a few percent and never fall below it.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "lenet_flops", os.path.join(BENCH, "flops", "lenet.py"))
lenet = importlib.util.module_from_spec(spec)
spec.loader.exec_module(lenet)

from reference import common as ref  # noqa: E402


def _params(img, ch):
    s2 = ((img - 4) // 2 - 4) // 2
    z = jnp.zeros
    return {"c1": {"w": z((5, 5, ch, 6)), "b": z((6,))},
            "c2": {"w": z((5, 5, 6, 16)), "b": z((16,))},
            "f1": {"w": z((16 * s2 * s2, 120)), "b": z((120,))},
            "f2": {"w": z((120, 84)), "b": z((84,))},
            "f3": {"w": z((84, 10)), "b": z((10,))}}


@pytest.mark.parametrize("img,ch", [(28, 1), (32, 3)])
def test_train_step_flops_match_xla(img, ch):
    batch = 64
    p = _params(img, ch)
    x = jnp.zeros((batch, img, img, ch))
    y = jnp.zeros((batch,), jnp.int32)

    def step(p, x, y):
        g = jax.grad(ref.xent)(p, x, y)
        return jax.tree_util.tree_map(lambda a, b: a - 0.01 * b, p, g)

    xla = jax.jit(step).lower(p, x, y).compile().cost_analysis()["flops"]
    ours = batch * lenet.train_step(img, ch)
    assert ours <= xla <= 1.05 * ours, (ours, xla)


@pytest.mark.parametrize("img,ch", [(28, 1), (32, 3)])
def test_forward_flops_match_xla(img, ch):
    batch = 64
    x = jnp.zeros((batch, img, img, ch))
    xla = jax.jit(ref.forward).lower(_params(img, ch), x).compile() \
        .cost_analysis()["flops"]
    ours = batch * lenet.forward(img, ch)
    assert ours <= xla <= 1.05 * ours, (ours, xla)


def test_published_widths():
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        _params(28, 1))) == 44426
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        _params(32, 3))) == 62006
