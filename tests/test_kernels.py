"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps, bit-exactness."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import numpy as np
import pytest

from repro.core import FMT_CIFAR, FMT_IMAGENET, EMFormat, QuantConfig, lowbit_matmul
from repro.kernels import lowbit_matmul_fused, mls_matmul_pallas, mls_quantize_pallas
from repro.kernels.ref import decode_frac_int, mls_matmul_ref, quantize_ref


@pytest.mark.parametrize("shape", [(128, 128), (256, 384), (64, 256)])
@pytest.mark.parametrize("fmt", [FMT_IMAGENET, FMT_CIFAR, EMFormat(2, 2)])
def test_quantize_kernel_matches_ref(shape, fmt):
    x = jax.random.normal(jax.random.key(0), shape) * 3.0
    bm = min(128, shape[0])
    codes_k, sg_k, st_k = mls_quantize_pallas(x, fmt, k_block=128, block_m=bm)
    r_u8 = jnp.full(shape, 127, dtype=jnp.uint8)
    codes_r, sg_r, st_r = quantize_ref(x, fmt, 128, r_u8=r_u8)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_array_equal(np.asarray(sg_k), np.asarray(sg_r))
    assert float(st_k) == float(st_r)


def test_quantize_kernel_stochastic_reproducible():
    x = jax.random.normal(jax.random.key(1), (128, 256))
    a = mls_quantize_pallas(x, FMT_IMAGENET, key=jax.random.key(7))
    b = mls_quantize_pallas(x, FMT_IMAGENET, key=jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    c = mls_quantize_pallas(x, FMT_IMAGENET, key=jax.random.key(8))
    assert np.any(np.asarray(a[0]) != np.asarray(c[0]))


@pytest.mark.parametrize("mnk", [(128, 128, 128), (128, 256, 384),
                                 (256, 128, 128)])
@pytest.mark.parametrize("fmt", [FMT_IMAGENET, FMT_CIFAR])
def test_matmul_kernel_bitexact_vs_ref(mnk, fmt):
    m, n, k = mnk
    x = jax.random.normal(jax.random.key(0), (m, k)) * 2
    w = jax.random.normal(jax.random.key(1), (k, n)) * 0.1
    xc, xsg, xst = mls_quantize_pallas(x, fmt, block_m=min(128, m))
    wc, wsgT, wst = mls_quantize_pallas(w.T, fmt, block_m=min(128, n))
    y_k = mls_matmul_pallas(xc, xsg, xst, wc.T, wsgT.T, wst, fmt)
    y_r = mls_matmul_ref(xc, xsg, xst, wc.T, wsgT.T, wst, fmt, 128)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_decode_frac_int_bounds():
    """Decoded integer fractions respect the paper's §V-C bit-width."""
    fmt = FMT_IMAGENET
    codes = jnp.arange(256, dtype=jnp.uint8)
    f = np.asarray(decode_frac_int(codes, fmt))
    assert np.abs(f).max() < 2 ** (fmt.m + 2**fmt.e - 1)


@pytest.mark.parametrize("shape", [(100, 200, 72), (128, 128, 128),
                                   (33, 77, 190)])
def test_fused_matmul_padding_and_accuracy(shape):
    m, k, n = shape
    x = jax.random.normal(jax.random.key(2), (m, k))
    w = jax.random.normal(jax.random.key(3), (k, n)) * 0.1
    y = lowbit_matmul_fused(x, w, None, fmt=FMT_IMAGENET)
    assert y.shape == (m, n)
    yref = x @ w
    rel = float(jnp.linalg.norm(y - yref) / jnp.linalg.norm(yref))
    assert rel < 0.08, rel


def test_fused_matches_core_fakequant():
    """Kernel quantized-domain GEMM ~= core fake-quant path (same grouping;
    differences only from tie-rounding in the r-source representation)."""
    x = jax.random.normal(jax.random.key(4), (128, 256)) * 2
    w = jax.random.normal(jax.random.key(5), (256, 128)) * 0.05
    y_k = lowbit_matmul_fused(x, w, None, fmt=FMT_IMAGENET)
    cfg = QuantConfig(fmt=FMT_IMAGENET, stochastic=False, grouping="nc")
    y_c = lowbit_matmul(x, w, None, cfg)
    rel = float(jnp.linalg.norm(y_k - y_c) / jnp.linalg.norm(y_c))
    assert rel < 0.01, rel


# ---------------------------------------------------------------------------
# grouping as a first-class kernel parameter (paper Table IV)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("grouping", ["nc", "c", "n", "none"])
def test_quantize_kernel_groupings_match_ref(grouping):
    x = jax.random.normal(jax.random.key(10), (96, 256)) * 3.0
    codes_k, sg_k, st_k = mls_quantize_pallas(
        x, FMT_IMAGENET, k_block=64, grouping=grouping)
    r_u8 = jnp.full(x.shape, 127, dtype=jnp.uint8)
    codes_r, sg_r, st_r = quantize_ref(
        x, FMT_IMAGENET, 64, r_u8=r_u8, grouping=grouping)
    assert sg_k.shape == sg_r.shape  # the grouping's compact layout
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_array_equal(np.asarray(sg_k), np.asarray(sg_r))
    assert float(st_k) == float(st_r)


@pytest.mark.parametrize("grouping", ["nc", "c", "n", "none"])
def test_fused_matmul_groupings_bitexact_vs_ref(grouping):
    from repro.kernels.lowbit_conv import REF_BACKEND, qd_gemm

    x = jax.random.normal(jax.random.key(11), (96, 256))
    w = jax.random.normal(jax.random.key(12), (256, 80)) * 0.1
    y_k = lowbit_matmul_fused(
        x, w, None, fmt=FMT_IMAGENET, k_block=64, block_m=64, block_n=64,
        grouping=grouping)
    y_r = qd_gemm(
        x, w, None, None, fmt=FMT_IMAGENET, k_block=64, block_m=64,
        block_n=64, grouping=grouping, backend=REF_BACKEND)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_grouping_changes_executed_scale_layout():
    """A non-"nc" grouping must change the group-scale BlockSpecs of the
    *executed* Pallas GEMM, not just the python-level arrays."""
    from repro.analysis.kernel_verify import find_pallas_eqns

    def sg_block_shapes(grouping):
        def fn(x, w):
            return lowbit_matmul_fused(
                x, w, None, fmt=FMT_IMAGENET, k_block=64, block_m=64,
                block_n=64, grouping=grouping, interpret=True)
        cj = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((128, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 64), jnp.float32))
        gemm_eqn = find_pallas_eqns(cj.jaxpr)[-1]  # quantize, quantize, gemm
        gm = gemm_eqn.params["grid_mapping"]
        # operands: x_codes, x_sg, w_codes, w_sg, st
        return tuple(
            tuple(1 if isinstance(b, pl.Squeezed) else b.block_size
                  for b in gm.block_mappings[i].block_shape)
            for i in (1, 3))

    # K=256 is one contraction tile of four 64-wide groups: "nc" scales
    # arrive as (tile, rows, groups) / (tile, groups, cols) blocks, "n" as
    # row / column blocks, "c" and "none" as whole SMEM arrays of scalars.
    assert sg_block_shapes("nc") == ((1, 64, 4), (1, 4, 64))
    assert sg_block_shapes("c") == ((4,), (4,))
    assert sg_block_shapes("n") == ((64, 1), (1, 64))
    assert sg_block_shapes("none") == ((1,), (1,))
    # "n" delivers the same block shape as "nc" but from a (M, 1) array —
    # the full-array layouts must differ
    def sg_array_shapes(grouping):
        _, sg, _ = mls_quantize_pallas(
            jnp.ones((128, 256)), FMT_IMAGENET, 64, grouping=grouping)
        return tuple(sg.shape)

    assert sg_array_shapes("nc") == (128, 4)
    assert sg_array_shapes("n") == (128, 1)
    assert sg_array_shapes("c") == (1, 4)
    assert sg_array_shapes("none") == (1, 1)


# ---------------------------------------------------------------------------
# ragged shapes: pad-and-slice vs ValueError (the two failure-mode paths)
# ---------------------------------------------------------------------------
def test_matmul_kernel_ragged_mn_pads_and_slices():
    """Ragged M/N against the block tiling is handled exactly by
    pad-and-slice inside the kernel wrapper."""
    m, k, n = 100, 128, 72  # M, N not multiples of the 64-blocks
    x = jax.random.normal(jax.random.key(13), (m, k)) * 2
    w = jax.random.normal(jax.random.key(14), (k, n)) * 0.1
    xc, xsg, xst = mls_quantize_pallas(x, FMT_IMAGENET, 64, block_m=64)
    wc, wsgT, wst = mls_quantize_pallas(w.T, FMT_IMAGENET, 64, block_m=64)
    y = mls_matmul_pallas(
        xc, xsg, xst, wc.T, wsgT.T, wst, FMT_IMAGENET, k_block=64,
        block_m=64, block_n=64)
    assert y.shape == (m, n)
    y_r = mls_matmul_ref(xc, xsg, xst, wc.T, wsgT.T, wst, FMT_IMAGENET, 64)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))


def test_matmul_kernel_ragged_k_raises_with_guidance():
    """K % k_block != 0 is a group-layout mismatch: a ValueError naming the
    shape, the block, and the nearest legal block."""
    xc = jnp.zeros((8, 100), jnp.uint8)
    wc = jnp.zeros((100, 8), jnp.uint8)
    with pytest.raises(ValueError) as e:
        mls_matmul_pallas(
            xc, jnp.ones((8, 1)), jnp.float32(1.0),
            wc, jnp.ones((1, 8)), jnp.float32(1.0),
            FMT_IMAGENET, k_block=64)
    msg = str(e.value)
    assert "K=100" in msg and "k_block=64" in msg and "50" in msg


def test_quantize_kernel_ragged_k_raises():
    with pytest.raises(ValueError, match="multiple of k_block"):
        mls_quantize_pallas(jnp.ones((8, 100)), FMT_IMAGENET, k_block=64)


def test_matmul_kernel_rejects_wrong_sg_layout():
    """Scales in the wrong compact layout for the grouping are rejected."""
    xc = jnp.zeros((64, 128), jnp.uint8)
    wc = jnp.zeros((128, 64), jnp.uint8)
    with pytest.raises(ValueError, match="layout mismatch"):
        mls_matmul_pallas(
            xc, jnp.ones((64, 2)), jnp.float32(1.0),  # "nc" x-layout
            wc, jnp.ones((2, 64)), jnp.float32(1.0),
            FMT_IMAGENET, k_block=64, grouping="c")  # but "c" requested
