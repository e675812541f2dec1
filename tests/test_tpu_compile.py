"""Compile the main path for one described TPU v5e chip, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached: it refuses what the chip would refuse — a Mosaic
kernel whose blocks the XLA layout does not match, a kernel tile that
overflows VMEM, a program that overflows HBM.  Interpret-mode kernel tests
cannot see any of these.  Nothing runs here, so these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.  Code that asks ``jax.default_backend()`` still sees
the CPU, so the tests steer the kernel wrappers out of interpret mode
themselves.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.fedhc import FLRunConfig
from repro.kernels import ops
from repro.kernels.kmeans import kmeans_assign
from repro.kernels.weighted_agg import weighted_agg_multi

V5E_HBM_BYTES = 16e9
HBM_HEADROOM_BYTES = 4e9        # chip_smoke.py phase A leaves this free

# LeNet's parameter leaves at the paper's mnist-like widths (28x28x1, 10
# classes), flattened: conv1 w/b, conv2 w/b, fc1 w/b, fc2 w/b, fc3 w/b
LENET_LEAF_WIDTHS = (150, 6, 2400, 16, 30720, 120, 10080, 84, 840, 10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # no fallback: where the TPU compiler cannot describe a v5e, these
    # checks fail rather than go quiet
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [800, 10000])
def test_kmeans_assign_compiles(one_chip, n):
    compiled = jax.jit(
        lambda x, c: kmeans_assign(x, c, interpret=False)).lower(
        _shape((n, 3), one_chip), _shape((4, 3), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [800, 2500])
def test_weighted_agg_multi_compiles_at_lenet_widths(one_chip, c):
    """C=2500 is one chip's share of N=10k over four chips: a whole-C
    tile ran out of VMEM there."""
    fn = jax.jit(lambda s, w: weighted_agg_multi(s, w, interpret=False))
    for p in LENET_LEAF_WIDTHS:
        compiled = fn.lower(_shape((c, p), one_chip),
                            _shape((c, 4), one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text(), p


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
def test_paper_scale_fedhc_scan_fits_one_v5e(one_chip, monkeypatch, pallas):
    """chip_smoke.py phase A/B: fedhc at N=800, K=4, batch 64, 128
    samples per client, 2 local steps, microbatch 100 compiles for one
    v5e and leaves 4 GB of its 16 GB HBM free; the Pallas route puts its
    kernels in the compiled scan."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = FLRunConfig(method="fedhc", num_clients=800, num_clusters=4,
                      rounds=10, rounds_per_global=5, local_steps=2,
                      batch_size=64, lr=0.01, samples_per_client=128,
                      eval_every=5, client_microbatch=100,
                      use_pallas_kernels=pallas)
    shapes = jax.tree_util.tree_map(
        lambda x: _shape(x.shape, one_chip, x.dtype),
        jax.eval_shape(lambda: engine.setup(cfg)))
    compiled = engine._scan_fn(cfg).lower(*shapes).compile()
    ma = compiled.memory_analysis()
    need = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert need + HBM_HEADROOM_BYTES <= V5E_HBM_BYTES, need
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == pallas
    # the minibatch gather copies whole 784-wide sample rows; gathered
    # as (1, 28, 28) slabs of the 4-D pool it picks lanes, the sample
    # axis being minor in the TPU's layout of that pool
    sizes = set(re.findall(r" gather\(.*slice_sizes=(\{[\d,]*\})"
                           r".*op_name=\"[^\"]*fed_step/batches/", text))
    assert "{1,784}" in sizes and "{1,28,28}" not in sizes, sizes
