"""Quantized-domain low-bit convolution via im2col / implicit GEMM.

This is the training hot path for paper Alg. 1 running on the **real**
quantized-domain Pallas pipeline (``mls_quantize_pallas`` ->
``mls_matmul_pallas``) instead of fake-quant + ``lax.conv``.  All three
training convolutions are lowered to MLS GEMMs over the im2col layout:

    forward : Z  = Cols(qA) @ qW            (Alg. 1 l.4)
    wgrad   : G  = Cols(qA)^T @ qE          (Alg. 1 l.13)
    dgrad   : dA = col2im(qE @ qW^T), STE   (Alg. 1 l.15-16)

Each GEMM dynamically quantizes its operands with scaling groups of
``k_block`` elements **along its own contraction axis**, so group boundaries
coincide with the GEMM's VMEM contraction tiles (the matmul analogue of the
paper's (n, c) conv grouping; the contraction axis plays the role of the
input channel).  That means the three GEMMs use three different group
layouts of the same logical operands — the per-GEMM dynamic-quantization
cost the paper budgets in Alg. 1.

Every function here is written against an abstract (quantize, matmul)
backend pair.  ``lowbit_conv_fused`` binds the Pallas kernels;
``lowbit_conv_fused_ref`` / ``conv_fused_grads_ref`` bind the pure-jnp
oracles from :mod:`repro.kernels.ref` through the *same* layout/padding
code, so kernel-vs-oracle tests assert bit-identical outputs and gradients.

``QuantConfig.grouping`` is honored end to end: each GEMM quantizes its
operands in the matmul analogue of the paper's Table IV layout ("nc" per
(row, k-block), "c" per k-block shared across rows, "n" per row/column,
"none" tensor-wise) and the Pallas GEMM consumes the matching compact
group-scale layout.  Output tilings left unset on the config resolve
through the autotuner cache (:mod:`repro.kernels.autotune`).

The forward conv has two interchangeable lowerings on the pallas backend:
``"im2col"`` (materialized patch matrix, any ``k_block``) and
``"implicit"`` (:mod:`repro.kernels.implicit_conv`: a single fused kernel
that walks the NCHW activation and quantizes in the GEMM prologue — no
patch matrix, activations read from HBM once).  ``QuantConfig.conv_impl``
/ the ``REPRO_CONV_IMPL`` env pick explicitly; ``"auto"`` resolves through
the tuned cache and falls back to implicit-when-legal.  The implicit
layout requires ``k_block = cb*kh*kw`` with ``cb | C`` (groups are whole
channels' taps), so impl selection never changes quantization semantics.
When it is active with ``grouping="none"`` and deterministic rounding,
the weight-grad GEMM *reuses the forward activation codes*: tensor-wise
quantization commutes with the patch gather, so the codes are gathered
(1 byte/element) instead of re-quantizing the fp32 patch matrix.  Other
groupings re-quantize because the wgrad contraction runs along the patch
axis — a different group layout than the forward's.
"""
from __future__ import annotations

from functools import partial
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.formats import EMFormat, GS_FMT_DEFAULT
from repro.core.lowbit import QuantConfig, _maybe_key
from .implicit_conv import (
    conv_geometry,
    covered_tensor_scale,
    elementwise_codes,
    implicit_conv_forward,
    patches_u8,
    resolve_conv_blocks,
    resolve_conv_impl,
)
from .mls_matmul import mls_matmul_pallas
from .mls_quantize import mls_quantize_pallas
from .ref import mls_matmul_ref, quantize_ref

__all__ = [
    "qd_gemm",
    "lowbit_conv_fused",
    "lowbit_conv_fused_ref",
    "conv_fused_grads_ref",
    "lowbit_matmul_qd",
    "matmul_qd_ref",
    "matmul_qd_grads_ref",
]


# ---------------------------------------------------------------------------
# Backend: (quantize, matmul) implementation pair
# ---------------------------------------------------------------------------
class QDBackend(NamedTuple):
    """A quantized-domain GEMM implementation.

    ``quantize(x2d, fmt, k_block, gs_fmt, key, block_m, grouping, interpret)``
        -> (codes u8 (M, K), s_g f32 in the grouping's compact layout,
            s_t f32 scalar)
    ``matmul(xc, xsg, xst, wc, wsg, wst, fmt, k_block, bm, bn, grouping,
    interpret)`` -> f32 (M, N)
    """

    quantize: Callable
    matmul: Callable


def _pallas_quantize(x2d, fmt, k_block, gs_fmt, key, block_m, grouping,
                     interpret):
    return mls_quantize_pallas(
        x2d, fmt, k_block, gs_fmt, key, block_m=block_m, interpret=interpret,
        grouping=grouping,
    )


def _pallas_matmul(xc, xsg, xst, wc, wsg, wst, fmt, k_block, bm, bn, grouping,
                   interpret):
    return mls_matmul_pallas(
        xc, xsg, xst, wc, wsg, wst, fmt,
        k_block=k_block, block_m=bm, block_n=bn, grouping=grouping,
        interpret=interpret,
    )


def _ref_quantize(x2d, fmt, k_block, gs_fmt, key, block_m, grouping,
                  interpret):
    # mirror the kernel's stochastic-rounding source exactly: uint8 draws
    # from `key`, and the r = 127 (~nearest) constant when key is None.
    if key is None:
        r_u8 = jnp.full(x2d.shape, 127, dtype=jnp.uint8)
    else:
        r_u8 = jax.random.randint(key, x2d.shape, 0, 256, dtype=jnp.int32).astype(
            jnp.uint8
        )
    return quantize_ref(
        x2d, fmt, k_block, gs_fmt=gs_fmt, r_u8=r_u8, grouping=grouping
    )


def _ref_matmul(xc, xsg, xst, wc, wsg, wst, fmt, k_block, bm, bn, grouping,
                interpret):
    return mls_matmul_ref(xc, xsg, xst, wc, wsg, wst, fmt, k_block)


PALLAS_BACKEND = QDBackend(_pallas_quantize, _pallas_matmul)
REF_BACKEND = QDBackend(_ref_quantize, _ref_matmul)


def _interpret(cfg: QuantConfig) -> bool | None:
    """Per-config interpret override; ``None`` defers to the process-wide
    switch (:func:`repro.kernels.runtime.resolve_interpret`)."""
    return cfg.pallas_interpret


# ---------------------------------------------------------------------------
# Core quantized-domain GEMM with padding to tile/group multiples
# ---------------------------------------------------------------------------
def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def qd_gemm(
    x2d: jax.Array,
    w2d: jax.Array,
    key_x: jax.Array | None,
    key_w: jax.Array | None,
    *,
    fmt: EMFormat,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    k_block: int = 128,
    block_m: int | None = None,
    block_n: int | None = None,
    grouping: str = "nc",
    backend: QDBackend = PALLAS_BACKEND,
    interpret: bool | None = None,
) -> jax.Array:
    """Dynamically quantize ``x (M,K)`` / ``w (K,N)`` and contract.

    Scaling groups follow ``grouping`` on both operands (each along its own
    contraction axis).  Output tiles left at ``None`` resolve through the
    autotuner cache on the *logical* (M, K, N) shape (explicit override >
    cache hit > proven-legal default).  Both operands are zero-padded to
    tile/group multiples (exact: padded codes are 0 so their products
    vanish, zero rows/columns are cropped from the output, and zero rows
    never raise a cross-row group maximum).  The weight operand is
    quantized transposed so its scaling groups run along K, then its
    codes/scales are transposed into the (K, N)-oriented layout the GEMM
    consumes (a plain transpose is exactly the GEMM-side compact layout
    for every grouping).
    """
    M, K = x2d.shape
    K2, N = w2d.shape
    assert K == K2, (x2d.shape, w2d.shape)
    if block_m is None or block_n is None:
        from .autotune import resolve_block_config  # lazy: avoids a cycle

        cfg = resolve_block_config(
            "gemm", (M, K, N), fmt, grouping,
            k_block=k_block, block_m=block_m, block_n=block_n,
        )
        block_m, block_n = cfg.block_m, cfg.block_n
    xp = _pad_to(x2d.astype(jnp.float32), block_m, k_block)
    wp = _pad_to(w2d.astype(jnp.float32), k_block, block_n)
    xc, xsg, xst = backend.quantize(
        xp, fmt, k_block, gs_fmt, key_x, block_m, grouping, interpret
    )
    wc, wsgT, wst = backend.quantize(
        wp.T, fmt, k_block, gs_fmt, key_w, block_n, grouping, interpret
    )
    y = backend.matmul(
        xc, xsg, xst, wc.T, wsgT.T, wst, fmt, k_block, block_m, block_n,
        grouping, interpret,
    )
    return y[:M, :N]


# ---------------------------------------------------------------------------
# im2col layout
# ---------------------------------------------------------------------------
# The patch gather is an identity-filter convolution: at the TPU's default
# precision it would round activations to bf16 before they are quantized.
_EXACT = jax.lax.Precision.HIGHEST


def _im2col(x: jax.Array, ksize: tuple[int, int], stride, padding):
    """NCHW -> (N*OH*OW, C*kh*kw) patch matrix (+ output spatial dims).

    Feature order is (c, kh, kw), matching ``w.reshape(O, C*kh*kw)`` of an
    OIHW weight, so conv == cols @ w_mat.T.
    """
    p = jax.lax.conv_general_dilated_patches(
        x.astype(jnp.float32), ksize, stride, padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=_EXACT,
    )
    n, ckk, oh, ow = p.shape
    return p.transpose(0, 2, 3, 1).reshape(n * oh * ow, ckk), (n, oh, ow)


def _col2im(dcols: jax.Array, x_shape, ksize, stride, padding, out_hw):
    """Exact transpose of :func:`_im2col` (scatter-add of patch cotangents)."""
    n, oh, ow = out_hw
    ckk = dcols.shape[1]
    dpatch = dcols.reshape(n, oh, ow, ckk).transpose(0, 3, 1, 2)

    def patches(a):
        return jax.lax.conv_general_dilated_patches(
            a, ksize, stride, padding,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=_EXACT,
        )

    transpose = jax.linear_transpose(
        patches, jax.ShapeDtypeStruct(x_shape, jnp.float32)
    )
    (dx,) = transpose(dpatch)
    return dx


# ---------------------------------------------------------------------------
# Fused conv: forward and backward pipelines (backend-parameterized)
# ---------------------------------------------------------------------------
def _gemm_kwargs(cfg: QuantConfig, backend: QDBackend):
    return dict(
        fmt=cfg.fmt, gs_fmt=cfg.gs_fmt, k_block=cfg.k_block,
        block_m=cfg.block_m, block_n=cfg.block_n, grouping=cfg.grouping,
        backend=backend, interpret=_interpret(cfg),
    )


def _conv_fwd_impl(x, w, key, stride, padding, cfg, backend):
    o = w.shape[0]
    if backend is PALLAS_BACKEND:
        geom = conv_geometry(x.shape, w.shape, stride, padding)
        if resolve_conv_impl(geom, cfg) == "implicit":
            bh, bn = resolve_conv_blocks(geom, cfg)
            return implicit_conv_forward(
                x, w, _maybe_key(key, cfg, 0), _maybe_key(key, cfg, 1),
                stride, padding, fmt=cfg.fmt, gs_fmt=cfg.gs_fmt,
                k_block=cfg.k_block, bh=bh, block_n=bn,
                grouping=cfg.grouping, interpret=_interpret(cfg),
            )
    cols, (n, oh, ow) = _im2col(x, w.shape[2:], stride, padding)
    wmat = w.reshape(o, -1).T.astype(jnp.float32)  # (C*kh*kw, O)
    y2d = qd_gemm(
        cols, wmat, _maybe_key(key, cfg, 0), _maybe_key(key, cfg, 1),
        **_gemm_kwargs(cfg, backend),
    )
    return y2d.reshape(n, oh, ow, o).transpose(0, 3, 1, 2)


def _qd_gemm_precoded_x(
    xc: jax.Array, x_st: jax.Array, w2d: jax.Array, key_w, *, fmt, gs_fmt,
    k_block, block_m, block_n, interpret,
):
    """`qd_gemm` with the x operand already in u8 codes (tensor-wise
    scale ``x_st``, grouping "none") — the forward-code-reuse wgrad path.
    Padding/quantize/matmul mirror `qd_gemm` exactly, so the result is
    bit-identical to re-quantizing the fp32 operand with grouping "none"
    and deterministic rounding."""
    M, K = xc.shape
    K2, N = w2d.shape
    assert K == K2, (xc.shape, w2d.shape)
    if block_m is None or block_n is None:
        from .autotune import resolve_block_config  # lazy: avoids a cycle

        bc = resolve_block_config(
            "gemm", (M, K, N), fmt, "none",
            k_block=k_block, block_m=block_m, block_n=block_n,
        )
        block_m, block_n = bc.block_m, bc.block_n
    xcp = _pad_to(xc, block_m, k_block)  # zero codes decode to 0 — exact
    wp = _pad_to(w2d.astype(jnp.float32), k_block, block_n)
    wc, wsgT, wst = _pallas_quantize(
        wp.T, fmt, k_block, gs_fmt, key_w, block_n, "none", interpret
    )
    ones = jnp.ones((1, 1), jnp.float32)
    y = _pallas_matmul(
        xcp, ones, x_st, wc.T, wsgT.T, wst, fmt, k_block, block_m, block_n,
        "none", interpret,
    )
    return y[:M, :N]


def _conv_bwd_impl(x, w, g, key, stride, padding, cfg, backend):
    o = w.shape[0]
    ksize = w.shape[2:]
    geom = conv_geometry(x.shape, w.shape, stride, padding)
    n, oh, ow = geom.n, geom.oh, geom.ow
    e2d = g.transpose(0, 2, 3, 1).reshape(-1, o).astype(jnp.float32)
    wmat = w.reshape(o, -1).astype(jnp.float32)  # (O, C*kh*kw)
    kw = _gemm_kwargs(cfg, backend)
    # G = Cols(qA)^T @ qE: contraction over the N*OH*OW patches (Alg. 1 l.13)
    reuse_codes = (
        backend is PALLAS_BACKEND
        and cfg.grouping == "none"
        and _maybe_key(key, cfg, 2) is None
        and resolve_conv_impl(geom, cfg) == "implicit"
    )
    if reuse_codes:
        # Tensor-wise quantization commutes with the patch gather, so the
        # forward activation codes are gathered as u8 instead of
        # re-quantizing the fp32 patch matrix (bit-identical to qd_gemm on
        # cols.T with grouping "none" + nearest rounding).
        s_t, xp = covered_tensor_scale(x, geom)
        colsT_codes = patches_u8(elementwise_codes(xp, s_t, cfg.fmt), geom).T
        dwmat = _qd_gemm_precoded_x(
            colsT_codes, s_t, e2d, _maybe_key(key, cfg, 3),
            fmt=cfg.fmt, gs_fmt=cfg.gs_fmt, k_block=cfg.k_block,
            block_m=cfg.block_m, block_n=cfg.block_n,
            interpret=_interpret(cfg),
        )
    else:
        cols, _ = _im2col(x, ksize, stride, padding)
        dwmat = qd_gemm(
            cols.T, e2d, _maybe_key(key, cfg, 2), _maybe_key(key, cfg, 3),
            **kw,
        )  # (C*kh*kw, O)
    dw = dwmat.T.reshape(w.shape)
    # dA = qE @ qW^T: contraction over output channels, then col2im + STE
    dcols = qd_gemm(
        e2d, wmat, _maybe_key(key, cfg, 4), _maybe_key(key, cfg, 5), **kw
    )  # (N*OH*OW, C*kh*kw)
    dx = _col2im(dcols, x.shape, ksize, stride, padding, (n, oh, ow))
    return dx, dw


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def lowbit_conv_fused(x, w, key, stride, padding, cfg: QuantConfig):
    """NCHW conv running all three training GEMMs in the MLS quantized
    domain through the Pallas kernels (paper Alg. 1 on real arithmetic).

    ``x``: (N, C, H, W); ``w``: (O, C, kh, kw); ``stride`` a 2-tuple;
    ``padding`` "SAME"/"VALID" or explicit pairs.  Output is fp32
    (N, O, OH, OW).  Gradients follow Alg. 1 with STE: each backward GEMM
    re-quantizes its operands from float in its own contraction-aligned
    group layout.
    """
    return _conv_fwd_impl(x, w, key, stride, padding, cfg, PALLAS_BACKEND)


def _lcf_fwd(x, w, key, stride, padding, cfg: QuantConfig):
    y = _conv_fwd_impl(x, w, key, stride, padding, cfg, PALLAS_BACKEND)
    return y, (x, w, key)


def _lcf_bwd(stride, padding, cfg: QuantConfig, res, g):
    x, w, key = res
    dx, dw = _conv_bwd_impl(x, w, g, key, stride, padding, cfg, PALLAS_BACKEND)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


lowbit_conv_fused.defvjp(_lcf_fwd, _lcf_bwd)


def lowbit_conv_fused_ref(x, w, key, stride, padding, cfg: QuantConfig):
    """jnp-oracle forward: same layout code, ref quantize/matmul."""
    return _conv_fwd_impl(x, w, key, stride, padding, cfg, REF_BACKEND)


def conv_fused_grads_ref(x, w, g, key, stride, padding, cfg: QuantConfig):
    """jnp-oracle (dx, dw) for cotangent ``g`` (bit-exactness tests)."""
    return _conv_bwd_impl(x, w, g, key, stride, padding, cfg, REF_BACKEND)


# ---------------------------------------------------------------------------
# Fused matmul with the same three-GEMM quantized-domain training semantics
# ---------------------------------------------------------------------------
def _mm_fwd_impl(x, w, key, cfg, backend):
    x2d = x.reshape(-1, x.shape[-1])
    y2d = qd_gemm(
        x2d, w.astype(jnp.float32),
        _maybe_key(key, cfg, 0), _maybe_key(key, cfg, 1),
        **_gemm_kwargs(cfg, backend),
    )
    return y2d.reshape(*x.shape[:-1], w.shape[1])


def _mm_bwd_impl(x, w, g, key, cfg, backend):
    x2d = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    e2d = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    kw = _gemm_kwargs(cfg, backend)
    # dX = qE @ qW^T (contraction over output features)
    dx2d = qd_gemm(
        e2d, w.astype(jnp.float32).T,
        _maybe_key(key, cfg, 2), _maybe_key(key, cfg, 3), **kw,
    )
    # dW = qX^T @ qE (contraction over rows)
    dw = qd_gemm(
        x2d.T, e2d, _maybe_key(key, cfg, 4), _maybe_key(key, cfg, 5), **kw
    )
    return dx2d.reshape(x.shape), dw


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def lowbit_matmul_qd(x, w, key, cfg: QuantConfig):
    """``x (..., K) @ w (K, N)`` with all three training GEMMs in the MLS
    quantized domain (Pallas kernels) — the linear-layer analogue of
    :func:`lowbit_conv_fused`."""
    return _mm_fwd_impl(x, w, key, cfg, PALLAS_BACKEND)


def _lmq_fwd(x, w, key, cfg: QuantConfig):
    return _mm_fwd_impl(x, w, key, cfg, PALLAS_BACKEND), (x, w, key)


def _lmq_bwd(cfg: QuantConfig, res, g):
    x, w, key = res
    dx, dw = _mm_bwd_impl(x, w, g, key, cfg, PALLAS_BACKEND)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


lowbit_matmul_qd.defvjp(_lmq_fwd, _lmq_bwd)


def matmul_qd_ref(x, w, key, cfg: QuantConfig):
    return _mm_fwd_impl(x, w, key, cfg, REF_BACKEND)


def matmul_qd_grads_ref(x, w, g, key, cfg: QuantConfig):
    return _mm_bwd_impl(x, w, g, key, cfg, REF_BACKEND)
