"""Pallas TPU kernel: low-bit tensor GEMM in the MLS quantized domain.

Implements the paper's low-bit convolution arithmetic (Sec. V-B, Eq. 6-8)
adapted to TPU as a tiled matmul:

* **Intra-group MACs** (Eq. 7): packed uint8 codes are decoded to signed
  integer fractions ``F`` (``|F| < 2^(M + 2^E - 1)``) and contracted over one
  ``k_block``-wide scaling group with an MXU ``dot``.  Products are at most
  ``2M + 2^(E+1) - 2`` bits (14 for the paper's ImageNet format ⟨2,4⟩), so
  fp32 accumulation over a 128-deep group is **bit-exact integer
  arithmetic** — the TPU-native analogue of the paper's int accumulator
  (fp32 is exact below 2^24; 14-bit products x 128 depth = 21 bits).
* **Inter-group combine** (Eq. 8): the partial sum of each group is scaled
  by ``S_p = s_g^x * s_g^w`` — a ⟨Eg,2⟩ value, i.e. a sum of <= 3 shifted
  copies in the paper's adder tree; here an exact fp32 multiply — and
  accumulated across groups in the fp32 output tile (the "TreeAdd" level).
* The tensor scales ``s_t^x * s_t^w`` multiply the output tile once
  (paper Sec. V-B: tensor-wise scale folded out of the MAC array).

Grid: ``(M/bm, N/bn, K/kt)`` with the contraction innermost; the
contraction tile ``kt`` (:func:`repro.kernels.mls_quantize.k_tile`) holds a
whole number of scaling groups, which the kernel contracts one by one with
static slices, so group boundaries never straddle a VMEM tile.  The decoded
fractions enter the MXU as bf16 whenever they fit its 8-bit significand
(``M <= 7``, every paper format) — still exact integer arithmetic.

**Grouping is a first-class kernel parameter** (paper Table IV): the
group-scale operands arrive in the compact layout of the grouping (see
:func:`sg_shapes`) and are laid out per grouping for the TPU: per-row and
per-column scales as VMEM blocks whose minor dims are lane-dense or whole
(``"nc"`` reshaped to ``(K/kt, rows, groups)``), per-group scalars
(``"c"``, ``"none"``) as flat SMEM arrays.  The kernel body is
layout-generic: each group's scale pair broadcasts against the (bm, bn)
partial-product tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import EMFormat
from .mls_quantize import SMEM_SPEC, k_tile, padded_k
from .runtime import resolve_interpret

GROUPINGS = ("nc", "c", "n", "none")


def sg_shapes(
    grouping: str, M: int, N: int, n_kb: int
) -> tuple[tuple[int, int], tuple[int, int]]:
    """Compact group-scale layouts ``(x_sg, w_sg)`` for an (M, K, N) GEMM.

    ``"nc"``: x (M, K/kb), w (K/kb, N) — one scale per (row, k-block) /
    (k-block, column); ``"c"``: (1, K/kb) / (K/kb, 1); ``"n"``: (M, 1) /
    (1, N); ``"none"``: (1, 1) / (1, 1).
    """
    if grouping == "nc":
        return (M, n_kb), (n_kb, N)
    if grouping == "c":
        return (1, n_kb), (n_kb, 1)
    if grouping == "n":
        return (M, 1), (1, N)
    if grouping == "none":
        return (1, 1), (1, 1)
    raise ValueError(f"unknown grouping {grouping!r}; expected {GROUPINGS}")


def scale_operand(sg, side: str, grouping: str, nkt: int, gpt: int,
                  block: int, grid_axis: int):
    """Lay one operand's compact group scales out for the TPU kernels.

    ``side`` is ``"x"`` (rows x groups) or ``"w"`` (groups x columns);
    ``block`` is the row (x) or column (w) tile and ``grid_axis`` the grid
    axis that walks it.  Returns ``(array, BlockSpec, get)`` where
    ``get(ref, k, g)`` reads the scale of group ``g`` of contraction tile
    ``k`` as a (bm, 1) / (1, bn) column or row, or a scalar.
    """
    def at(idx):  # index map putting `idx` where the grid walks the tile
        def im(*ids):
            return idx(ids[grid_axis], ids[-1])
        return im

    if grouping == "nc":
        if side == "x":  # (M, nkb) -> (nkt, M, gpt)
            arr = sg.reshape(sg.shape[0], nkt, gpt).transpose(1, 0, 2)
            spec = pl.BlockSpec((1, block, gpt), at(lambda b, k: (k, b, 0)))
            return arr, spec, lambda ref, k, g: ref[0, :, g:g + 1]
        arr = sg.reshape(nkt, gpt, sg.shape[1])  # (nkb, N) -> (nkt, gpt, N)
        spec = pl.BlockSpec((1, gpt, block), at(lambda b, k: (k, 0, b)))
        return arr, spec, lambda ref, k, g: ref[0, g:g + 1, :]
    if grouping == "n":
        if side == "x":  # (M, 1)
            spec = pl.BlockSpec((block, 1), at(lambda b, k: (b, 0)))
        else:  # (1, N)
            spec = pl.BlockSpec((1, block), at(lambda b, k: (0, b)))
        return sg, spec, lambda ref, k, g: ref[...]
    flat = sg.reshape(-1)  # "c": one scalar per group; "none": one scalar
    if grouping == "c":
        return flat, SMEM_SPEC, lambda ref, k, g: ref[k * gpt + g]
    return flat, SMEM_SPEC, lambda ref, k, g: ref[0]


def mxu_dtype(fmt: EMFormat):
    """Operand dtype of the integer-fraction dot: bf16 holds every |F| <
    2^(M+1) << (2^E - 2) exactly while M + 1 <= 8 significand bits."""
    return jnp.bfloat16 if fmt.m <= 7 else jnp.float32


def fraction_dot(fx, fw, fmt: EMFormat):
    """Exact integer-fraction product on the MXU (fp32 accumulation)."""
    dt = mxu_dtype(fmt)
    return jnp.dot(
        fx.astype(dt), fw.astype(dt), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if dt == jnp.float32 else None,
    )


def _decode_frac(codes, fmt: EMFormat):
    """uint8 codes -> signed integer fractions as exact fp32 values."""
    c = codes.astype(jnp.int32)
    man = c & (2**fmt.m - 1)
    exp = (c >> fmt.m) & (2**fmt.e - 1)
    sign_bit = c >> (fmt.e + fmt.m)
    top = 2**fmt.e - 1
    is_denorm = exp == 0
    base = jnp.where(is_denorm, man, 2**fmt.m + man)
    shift = jnp.where(is_denorm, 0, top - exp)
    f = (base << shift).astype(jnp.float32)
    return jnp.where(sign_bit == 1, -f, f)


def _kernel(
    xc_ref, xsg_ref, wc_ref, wsg_ref, st_ref, out_ref, acc_ref, *, fmt, n_k,
    k_block, x_scale, w_scale,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fx = _decode_frac(xc_ref[...], fmt)  # (bm, kt) exact small ints
    fw = _decode_frac(wc_ref[...], fmt)  # (kt, bn)
    acc = acc_ref[...]
    for g in range(fx.shape[1] // k_block):  # the tile's scaling groups
        sl = slice(g * k_block, (g + 1) * k_block)
        # Intra-group integer MACs on the MXU (exact, see module doc).
        p = fraction_dot(fx[:, sl], fw[sl, :], fmt)  # (bm, bn)
        # Inter-group scale S_p = s_g^x * s_g^w (shift-add in HW, exact
        # here), broadcast against the (bm, bn) partial tile.
        acc = acc + p * (x_scale(xsg_ref, k, g) * w_scale(wsg_ref, k, g))
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _done():
        unit = 2.0 ** (2 * (fmt.e_min - fmt.m))
        out_ref[...] = acc_ref[...] * (st_ref[0, 0] * unit)


def _nearest_legal_block(extent: int, block: int) -> int:
    """Largest divisor of ``extent`` that is <= ``block`` (for error text)."""
    for b in range(min(block, extent), 0, -1):
        if extent % b == 0:
            return b
    return 1


def mls_matmul_pallas(
    x_codes: jax.Array,
    x_sg: jax.Array,
    x_st: jax.Array,
    w_codes: jax.Array,
    w_sg: jax.Array,
    w_st: jax.Array,
    fmt: EMFormat,
    k_block: int = 128,
    block_m: int = 128,
    block_n: int = 128,
    grouping: str = "nc",
    interpret: bool | None = None,
) -> jax.Array:
    """Quantized-domain GEMM: x (M, K) @ w (K, N) -> fp32 (M, N).

    Group scales arrive in the compact layout of ``grouping`` (see
    :func:`sg_shapes`); ``"nc"`` is the paper's default: ``x_sg``
    (M, K/k_block), ``w_sg`` (K/k_block, N).

    Ragged ``M``/``N`` (not multiples of the clamped block) are handled by
    zero-padding the codes and slicing the output — exact, since padded
    codes decode to 0 and contribute nothing.  A ``K`` that is not a
    multiple of ``k_block`` is a group-layout mismatch (the scales would
    not line up) and raises ``ValueError``.
    """
    M, K = x_codes.shape
    K2, N = w_codes.shape
    assert K == K2, (x_codes.shape, w_codes.shape)
    if K % k_block:
        raise ValueError(
            f"mls_matmul_pallas: contraction K={K} of shape "
            f"({M}, {K}, {N}) is not a multiple of k_block={k_block} "
            f"(group boundaries would not align); nearest legal k_block "
            f"is {_nearest_legal_block(K, k_block)} — re-quantize with a "
            f"dividing k_block or pad K to a multiple before quantizing"
        )
    nkb = K // k_block
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    exp_xsg, exp_wsg = sg_shapes(grouping, M, N, nkb)
    if tuple(x_sg.shape) != exp_xsg or tuple(w_sg.shape) != exp_wsg:
        raise ValueError(
            f"group-scale layout mismatch for grouping={grouping!r}: "
            f"expected x_sg {exp_xsg} / w_sg {exp_wsg}, got "
            f"{tuple(x_sg.shape)} / {tuple(w_sg.shape)}"
        )

    # Pad ragged M/N tails to block multiples and K to whole contraction
    # tiles (exact: zero codes decode to 0; padded scales are 1.0 so no
    # inf/nan can leak into 0 * sp).
    kp = padded_k(K, k_block)
    kt = k_tile(kp, k_block)
    pm, pn, pk, pg = (-M) % block_m, (-N) % block_n, kp - K, (kp - K) // k_block
    if pm or pk:
        x_codes = jnp.pad(x_codes, ((0, pm), (0, pk)))
    if pn or pk:
        w_codes = jnp.pad(w_codes, ((0, pk), (0, pn)))
    if grouping in ("nc", "n") and pm:
        x_sg = jnp.pad(x_sg, ((0, pm), (0, 0)), constant_values=1.0)
    if grouping in ("nc", "n") and pn:
        w_sg = jnp.pad(w_sg, ((0, 0), (0, pn)), constant_values=1.0)
    if grouping in ("nc", "c") and pg:
        x_sg = jnp.pad(x_sg, ((0, 0), (0, pg)), constant_values=1.0)
        w_sg = jnp.pad(w_sg, ((0, pg), (0, 0)), constant_values=1.0)
    Mp, Np = M + pm, N + pn
    nkt, gpt = kp // kt, kt // k_block

    st = (x_st * w_st).astype(jnp.float32).reshape(1, 1)
    x_sg, xsg_spec, x_scale = scale_operand(
        x_sg, "x", grouping, nkt, gpt, block_m, grid_axis=0)
    w_sg, wsg_spec, w_scale = scale_operand(
        w_sg, "w", grouping, nkt, gpt, block_n, grid_axis=1)
    kernel = functools.partial(
        _kernel, fmt=fmt, n_k=nkt, k_block=k_block, x_scale=x_scale,
        w_scale=w_scale)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // block_m, Np // block_n, nkt),
        in_specs=[
            pl.BlockSpec((block_m, kt), lambda i, j, k: (i, k)),
            xsg_spec,
            pl.BlockSpec((kt, block_n), lambda i, j, k: (k, j)),
            wsg_spec,
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x_codes, x_sg, w_codes, w_sg, st)
    return out[:M, :N] if (pm or pn) else out
