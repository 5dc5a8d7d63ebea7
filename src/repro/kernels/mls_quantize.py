"""Pallas TPU kernel: fused MLS dynamic quantization (paper Alg. 2).

One pass over the operand computes group maxima, the hardware-friendly
``<Eg,Mg>`` group scales (ceil-rounded), and the packed ``<Ex,Mx>`` element
codes with stochastic rounding — writing **1 byte per element** plus one
scale per group back to HBM (vs 4 bytes for the fp32 input): the
memory-traffic reduction that makes dynamic quantization cheap on TPU.

The tensor-wise scale ``s_t`` is a global reduction and is computed ahead of
the kernel (a cheap fused max-reduce); it enters the kernel via SMEM.

**Grouping** (paper Table IV) selects the scaling-group layout of a 2-D
``(M, K)`` operand (the GEMM orientation: rows x contraction):

* ``"nc"`` — one group per (row, ``k_block``-wide contraction block);
  scales (M, K/k_block), computed inside the kernel (the default).
* ``"n"``  — one group per row; scales (M, 1).  The row maximum spans every
  contraction tile, so it is a fused XLA reduction ahead of the kernel.
* ``"c"``  — one group per contraction block shared by *all* rows; scales
  (1, K/k_block).  The group max crosses row-block programs, so the compact
  scales are precomputed by a fused XLA reduction.
* ``"none"`` — tensor-wise only; group scales are exactly 1 (shape (1, 1)).

Scales, in the kernel or ahead of it, come from ``quantize_group_scale``
itself, so every grouping is bit-identical to
:func:`repro.kernels.ref.quantize_ref`.

Grid: ``(M/block_m, K/k_tile)``.  The contraction axis is tiled
(:func:`k_tile`), so VMEM holds one ``(block_m, k_tile)`` tile whatever K
is — a weight-gradient operand contracts over ``N*OH*OW`` (131072 for a
batch-128 CIFAR layer).  A tile holds a whole number of scaling groups,
which the kernel walks with static lane slices.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import EMFormat, GS_FMT_DEFAULT, exponent_fraction, pow2
from repro.core.quantize import quantize_group_scale
from .runtime import resolve_interpret

DEFAULT_BLOCK_M = 256

GROUPINGS = ("nc", "c", "n", "none")

_LANES = 128  # TPU vreg lane width: the minor dim of a VMEM tile
_FULL_K_MAX = 2048  # contraction extents up to this stay one whole tile

# Rounding offset of the round-to-nearest path: r_u8 = 127 in the
# stochastic formula below, i.e. u = (127 + 0.5) / 256 - 0.5.
_NEAREST = (127 + 0.5) / 256.0 - 0.5

SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def k_tile(K: int, k_block: int) -> int:
    """Contraction tile of the quantize/GEMM kernels for a ``K`` that is a
    multiple of ``k_block``: one scaling group when ``k_block`` is
    lane-aligned, the whole ``K`` when it is short, else the smallest
    lane-aligned whole number of groups (``lcm(k_block, 128)``)."""
    if k_block % _LANES == 0:
        return k_block
    if K <= _FULL_K_MAX:
        return K
    return math.lcm(k_block, _LANES)


def padded_k(K: int, k_block: int) -> int:
    """``K`` rounded up to whole scaling groups and whole :func:`k_tile`
    tiles.  Zero padding along K is exact for every grouping."""
    K = -(-K // k_block) * k_block
    kt = k_tile(K, k_block)
    return -(-K // kt) * kt


def rounding_offset(r_u8):
    """uint8 stochastic-rounding draws -> offsets u in (-1/2, 1/2).  The
    cast goes through int32: Mosaic has no direct uint8 -> fp32 convert."""
    return (r_u8.astype(jnp.int32).astype(jnp.float32) + 0.5) / 256.0 - 0.5


def _element_codes(x, r, denom, fmt: EMFormat):
    """Packed codes for one block given its scale denominator (Alg. 2
    l.9-16).  ``denom`` broadcasts against ``x`` ((bm, 1), (1,) or scalar);
    ``r`` is a :func:`rounding_offset` array, or ``None`` for nearest."""
    absx = jnp.abs(x)
    sign_bit = (x < 0).astype(jnp.int32)
    x_f = jnp.where(denom > 0, absx / jnp.where(denom > 0, denom, 1.0), 0.0)
    e_x, _ = exponent_fraction(x_f)
    e_eff = jnp.clip(e_x, fmt.e_min, -1)
    step = pow2(e_eff - fmt.m)
    q = jnp.floor(x_f / step + (_NEAREST if r is None else r) + 0.5)
    qmax = jnp.where(e_eff == -1, 2.0 ** (fmt.m + 1) - 1.0, 2.0 ** (fmt.m + 1))
    q = jnp.clip(q, 0.0, qmax)
    xbar = q * step

    e2, frac2 = exponent_fraction(xbar)
    is_normal = e2 >= fmt.e_min
    man = jnp.where(
        is_normal,
        jnp.floor((frac2 - 1.0) * 2.0**fmt.m + 0.5),
        jnp.floor(xbar * 2.0 ** (fmt.m - fmt.e_min) + 0.5),
    ).astype(jnp.int32)
    exp_stored = jnp.where(is_normal, -e2, 0)
    return (
        (sign_bit << (fmt.e + fmt.m)) | (exp_stored << fmt.m) | man
    ).astype(jnp.uint8)


def _quantize_block(x, r, s_t, fmt: EMFormat, gs_fmt: EMFormat):
    """Quantize one (block_m, group_width) group column -> (codes, s_g
    (block_m, 1))."""
    s_r = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    s_g, _, _ = quantize_group_scale(s_r / s_t, gs_fmt)
    return _element_codes(x, r, s_t * s_g, fmt), s_g


def _kernel(*refs, fmt, gs_fmt, k_block, grouping, stochastic):
    """Quantize one ``(block_m, k_tile)`` tile.  ``"nc"`` computes its
    group scales here and writes them to a ``(1, block_m, groups)`` block;
    the other groupings read precomputed scales (``"n"``: a (block_m, 1)
    VMEM block; ``"c"``/``"none"``: a flat SMEM array)."""
    it = iter(refs)
    x_ref = next(it)
    r_ref = next(it) if stochastic else None
    st_ref = next(it)
    sg_in = None if grouping == "nc" else next(it)
    codes_ref = next(it)
    sg_out = next(it) if grouping == "nc" else None
    s_t = st_ref[0, 0]
    x = x_ref[...]
    r = rounding_offset(r_ref[...]) if stochastic else None

    if grouping in ("n", "none"):  # one scale for the whole tile row
        s_g = sg_in[...] if grouping == "n" else sg_in[0]
        codes_ref[...] = _element_codes(x, r, s_t * s_g, fmt)
        return

    k = pl.program_id(1)
    gpt = x.shape[1] // k_block
    for g in range(gpt):  # static loop over the tile's scaling groups
        sl = slice(g * k_block, (g + 1) * k_block)
        rg = None if r is None else r[:, sl]
        if grouping == "nc":
            codes, s_g = _quantize_block(x[:, sl], rg, s_t, fmt, gs_fmt)
            sg_out[0, :, g:g + 1] = s_g
        else:  # "c"
            codes = _element_codes(x[:, sl], rg, s_t * sg_in[k * gpt + g], fmt)
        codes_ref[:, sl] = codes


def mls_quantize_pallas(
    x: jax.Array,
    fmt: EMFormat,
    k_block: int = 128,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    key: jax.Array | None = None,
    block_m: int = DEFAULT_BLOCK_M,
    interpret: bool | None = None,
    grouping: str = "nc",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize a 2-D ``(M, K)`` operand to packed MLS codes.

    Returns ``(codes uint8 (M, K), s_g f32, s_t f32 scalar)`` with ``s_g``
    in the compact layout of ``grouping`` (see the module docstring):
    (M, K/k_block), (1, K/k_block), (M, 1) or (1, 1).

    A ragged row count (``M`` not a multiple of the clamped ``block_m``) is
    zero-padded and sliced back — exact: zero rows quantize to zero codes
    and never contribute to any cross-row group maximum.  ``K`` must be a
    multiple of ``k_block`` (group boundaries), else ``ValueError``; it is
    zero-padded to whole contraction tiles the same way.
    """
    if grouping not in GROUPINGS:
        raise ValueError(
            f"unknown grouping {grouping!r}; expected one of {GROUPINGS}")
    M, K = x.shape
    if K % k_block:
        raise ValueError(
            f"mls_quantize_pallas: K={K} not a multiple of k_block="
            f"{k_block}; pad the operand (the fused ops do) or pick a "
            f"dividing k_block"
        )
    block_m = min(block_m, M)
    interpret = resolve_interpret(interpret)
    x = x.astype(jnp.float32)
    s_t = jnp.max(jnp.abs(x))
    s_t = jnp.where(s_t > 0, s_t, 1.0).reshape(1, 1)
    stochastic = key is not None
    r_u8 = None
    if stochastic:
        with jax.named_scope("rounding"):
            r_u8 = jax.random.randint(
                key, x.shape, 0, 256, dtype=jnp.int32).astype(jnp.uint8)

    kp = padded_k(K, k_block)
    kt = k_tile(kp, k_block)
    pm, pk = (-M) % block_m, kp - K
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
        if stochastic:
            r_u8 = jnp.pad(r_u8, ((0, pm), (0, pk)), constant_values=127)
    Mp = M + pm
    nkb, nkt, gpt = kp // k_block, kp // kt, kt // k_block

    tile = pl.BlockSpec((block_m, kt), lambda i, k: (i, k))
    in_specs = [tile] + ([tile] if stochastic else []) + [SMEM_SPEC]
    operands = [x] + ([r_u8] if stochastic else []) + [s_t]
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((Mp, kp), jnp.uint8)]
    if grouping == "nc":
        out_specs.append(
            pl.BlockSpec((1, block_m, gpt), lambda i, k: (k, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nkt, Mp, gpt), jnp.float32))
    else:
        # compact scales precomputed: the "n" row max spans every k-tile,
        # the "c" group max every row block.
        if grouping == "n":
            s_r = jnp.max(jnp.abs(x), axis=1, keepdims=True)
            s_g, _, _ = quantize_group_scale(s_r / s_t[0, 0], gs_fmt)
            in_specs.append(pl.BlockSpec((block_m, 1), lambda i, k: (i, 0)))
            operands.append(s_g)  # (Mp, 1)
        elif grouping == "c":
            s_r = jnp.max(jnp.abs(x), axis=0).reshape(nkb, k_block).max(axis=1)
            s_g, _, _ = quantize_group_scale(s_r / s_t[0, 0], gs_fmt)
            in_specs.append(SMEM_SPEC)
            operands.append(s_g)  # (nkb,)
        else:  # "none"
            s_g = jnp.ones((1,), jnp.float32)
            in_specs.append(SMEM_SPEC)
            operands.append(s_g)

    kernel = functools.partial(
        _kernel, fmt=fmt, gs_fmt=gs_fmt, k_block=k_block, grouping=grouping,
        stochastic=stochastic)
    res = pl.pallas_call(
        kernel,
        grid=(Mp // block_m, nkt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="mls_quantize",
    )(*operands)
    codes = res[0][:M, :K]
    if grouping == "nc":
        s_g = res[1].transpose(1, 0, 2).reshape(Mp, nkb)[:M, : K // k_block]
    elif grouping == "n":
        s_g = s_g[:M]
    elif grouping == "c":
        s_g = s_g[: K // k_block].reshape(1, -1)
    else:
        s_g = s_g.reshape(1, 1)
    return codes, s_g, s_t[0, 0]
