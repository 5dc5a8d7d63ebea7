"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib, so Mosaic lowers and compiles these
kernels for a described (not attached) v5e chip with no TPU present.  This
catches what interpret mode cannot: block shapes the TPU tiling refuses,
casts Mosaic lacks, in-kernel relayouts it cannot do, and blocks beyond
the chip's scoped VMEM.  Shapes are ResNet-20's at batch 128 (the paper's
CIFAR setting): 16 channels at 32x32, 32 at 16x16 and 64 at 8x8.

The topology is described inside a module-scoped fixture, never while the
module is imported, and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import FMT_IMAGENET
from repro.kernels import (
    implicit_conv_forward,
    mls_matmul_pallas,
    mls_quantize_pallas,
)
from repro.kernels.mls_matmul import sg_shapes

BATCH = 128
KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back persistent-cache entries
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


@pytest.mark.parametrize("shape,k_block", [
    ((BATCH * 32 * 32, 256), 128),  # forward im2col operand, stage 1
    ((144, BATCH * 32 * 32), 128),  # weight-gradient operand, stage 1
], ids=["forward", "wgrad"])
def test_quantize_compiles(one_chip, shape, k_block):
    _compile(one_chip, lambda x: mls_quantize_pallas(
        x, FMT_IMAGENET, k_block, key=KEY, block_m=128, interpret=False),
        (shape, jnp.float32))


@pytest.mark.parametrize("mkn,k_block,grouping", [
    ((BATCH * 32 * 32, 256, 128), 128, "nc"),  # forward GEMM, stage 1
    ((256, 131184, 128), 144, "c"),  # wgrad GEMM: K tiled, 8 groups a tile
], ids=["forward-nc", "wgrad-kb144-c"])
def test_matmul_compiles(one_chip, mkn, k_block, grouping):
    m, k, n = mkn
    xs, ws = sg_shapes(grouping, m, n, k // k_block)
    _compile(one_chip, lambda xc, xsg, wc, wsg: mls_matmul_pallas(
        xc, xsg, jnp.float32(1.0), wc, wsg, jnp.float32(1.0), FMT_IMAGENET,
        k_block=k_block, grouping=grouping, interpret=False),
        ((m, k), jnp.uint8), (xs, jnp.float32), ((k, n), jnp.uint8),
        (ws, jnp.float32))


@pytest.mark.parametrize("c,hw", [(16, 32), (32, 16), (64, 8)],
                         ids=["stage1", "stage2", "stage3"])
def test_implicit_conv_compiles(one_chip, c, hw):
    _compile(one_chip, lambda x, w: implicit_conv_forward(
        x, w, KEY, KEY, (1, 1), "SAME", fmt=FMT_IMAGENET, k_block=144,
        interpret=False),
        ((BATCH, c, hw, hw), jnp.float32), ((c, c, 3, 3), jnp.float32))
