"""Dynamic quantization to the MLS tensor format (paper Alg. 2).

The public entry points are

* :func:`mls_quantize`  — float tensor -> :class:`MLSTensor` (all levels of
  scaling + quantized elements, bit-exact fields).
* :func:`fake_quant`    — float tensor -> float tensor whose values lie
  exactly on the MLS grid (what the paper simulates on GPU).
* :func:`fake_quant_ste` — `fake_quant` with a straight-through estimator,
  used by the low-bit training ops (paper Alg. 1 line 16).

Grouping is expressed by a :class:`GroupSpec`: a per-axis block size.  Block
size 1 makes the axis a pure group axis (one group per index), block size ==
axis length reduces the whole axis into the group.  The paper's "nc" grouping
of a conv operand ``(N, C, H, W)`` is ``GroupSpec((1, 1, H, W))``; a matmul
operand ``(M, K)`` grouped per row and per 128-wide contraction block is
``GroupSpec((1, 128))``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from collections.abc import Sequence

import jax
import jax.numpy as jnp

from .formats import EMFormat, GS_FMT_DEFAULT, exponent_fraction, pow2, srandom_like

__all__ = [
    "GroupSpec",
    "MLSTensor",
    "mls_quantize",
    "fake_quant",
    "fake_quant_ste",
    "quantize_group_scale",
    "quantize_elements",
    "average_relative_error",
    "pack_elements",
    "unpack_elements",
]


# --------------------------------------------------------------------------
# Grouping
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """Per-axis block sizes defining scaling groups.

    ``block[i]`` elements along axis ``i`` share one group (together with the
    blocks of every other axis).  ``None`` means "whole axis in one group".
    """

    block: tuple[int | None, ...]

    def resolve(self, shape: Sequence[int]) -> tuple[int, ...]:
        if len(self.block) != len(shape):
            raise ValueError(f"GroupSpec rank {len(self.block)} != tensor rank {len(shape)}")
        out = []
        for b, d in zip(self.block, shape):
            b = d if b is None else min(b, d)
            if d % b != 0:
                # fall back to one group over the whole axis (coarser scaling,
                # still correct) — keeps odd feature widths working without
                # padding; the Pallas kernels pad instead.
                b = d
            out.append(b)
        return tuple(out)

    def group_shape(self, shape: Sequence[int]) -> tuple[int, ...]:
        return tuple(d // b for d, b in zip(shape, self.resolve(shape)))

    @staticmethod
    def per_tensor(rank: int) -> "GroupSpec":
        return GroupSpec((None,) * rank)

    @staticmethod
    def conv_nc(rank: int = 4) -> "GroupSpec":
        """Paper's best grouping: one group per (dim0, dim1) pair."""
        return GroupSpec((1, 1) + (None,) * (rank - 2))


def _split_axes(x: jax.Array, blocks: tuple[int, ...]):
    """Reshape (d0, d1, ...) -> (g0, b0, g1, b1, ...)."""
    new_shape = []
    for d, b in zip(x.shape, blocks):
        new_shape.extend((d // b, b))
    return x.reshape(new_shape)


def group_reduce_max(x: jax.Array, spec: GroupSpec) -> jax.Array:
    blocks = spec.resolve(x.shape)
    xs = _split_axes(x, blocks)
    axes = tuple(range(1, xs.ndim, 2))
    return jnp.max(xs, axis=axes)


def broadcast_groups(s: jax.Array, spec: GroupSpec, shape: Sequence[int]) -> jax.Array:
    """Broadcast a group-shaped array back to the full tensor shape."""
    blocks = spec.resolve(shape)
    expanded = s.reshape(tuple(v for g in s.shape for v in (g, 1)))
    tiled = jnp.broadcast_to(
        expanded, tuple(v for g, b in zip(s.shape, blocks) for v in (g, b))
    )
    return tiled.reshape(tuple(shape))


# --------------------------------------------------------------------------
# Scale / element quantizers
# --------------------------------------------------------------------------
def quantize_group_scale(s_gf: jax.Array, gs_fmt: EMFormat):
    """Quantize group/tensor scale ratios in (0, 1] (paper Alg. 2 l.4-8).

    Fractions are *ceil*-rounded so the quantized scale is >= the true ratio,
    guaranteeing normalized elements stay <= 1.  Returns ``(s_g, exp_g,
    man_g)`` where ``s_g = (1 + man_g/2^Mg) * 2^-exp_g`` exactly.
    """
    # fp32 cannot represent 2^e below ~2^-126; group ratios that small mean
    # "all-zero group", so clamping the exponent there is exact in effect.
    e_min = max(gs_fmt.e_min, -120)
    e, frac = exponent_fraction(s_gf)
    # values below the smallest normal scale are ceil'd up to it
    too_small = e < e_min
    e = jnp.clip(e, e_min, 0)
    frac = jnp.where(too_small, 1.0, frac)
    man = jnp.ceil((frac - 1.0) * 2.0**gs_fmt.m).astype(jnp.int32)
    # fraction overflow: man == 2^Mg means frac_q == 2 -> bump exponent
    overflow = man >= 2**gs_fmt.m
    man = jnp.where(overflow, 0, man)
    e = jnp.clip(jnp.where(overflow, e + 1, e), e_min, 0)
    s_g = (1.0 + man.astype(jnp.float32) * 2.0**-gs_fmt.m) * pow2(e)
    return s_g, (-e).astype(jnp.int32), man


def quantize_elements(
    x_f: jax.Array,
    fmt: EMFormat,
    r: jax.Array | None = None,
):
    """Quantize normalized magnitudes in [0, 1] to the <E,M> grid.

    Implements paper Alg. 2 lines 9-16: per-element exponent extraction,
    mantissa stochastic rounding (``r`` is the U[-1/2,1/2) tensor; ``None``
    means round-to-nearest), IEEE-754 gradual underflow at ``e_min`` and
    saturation at the top of the grid.  Returns ``(xbar, exp_stored, man)``
    with ``xbar`` the dequantized magnitude (exactly on the grid).
    """
    x_f = x_f.astype(jnp.float32)
    if fmt.e == 0:
        # plain fixed point: uniform grid man/2^M over [0, 1)
        step = jnp.float32(2.0**-fmt.m)
        scaled = x_f / step
        q = jnp.floor(scaled + (r if r is not None else 0.0) + 0.5)
        q = jnp.clip(q, 0.0, 2.0**fmt.m - 1.0)
        xbar = q * step
        e_eff = jnp.zeros_like(x_f, jnp.int32)
    else:
        e, _ = exponent_fraction(x_f)
        e_eff = jnp.clip(e, fmt.e_min, -1)
        # step = 2^(e_eff - M): grid spacing at this exponent level (covers
        # denormals too: at e_min the denormal step equals the normal step).
        step = pow2(e_eff - fmt.m)
        scaled = x_f / step
        if r is not None:
            q = jnp.floor(scaled + r + 0.5)
        else:
            q = jnp.floor(scaled + 0.5)
        # top-of-grid saturation: at e_eff == -1 the next exponent (0) does
        # not exist, clip to (2 - 2^-M) * 2^-1.  At lower exponents
        # q == 2^(M+1) legitimately rounds up into the next exponent level.
        qmax = jnp.where(e_eff == -1, 2.0 ** (fmt.m + 1) - 1.0,
                         2.0 ** (fmt.m + 1))
        q = jnp.clip(q, 0.0, qmax)
        xbar = q * step

    # exact storage fields from the on-grid value
    e2, frac2 = exponent_fraction(xbar)
    is_normal = e2 >= fmt.e_min
    man = jnp.where(
        is_normal,
        jnp.round((frac2 - 1.0) * 2.0**fmt.m),
        jnp.round(xbar * 2.0 ** (fmt.m - fmt.e_min)),
    ).astype(jnp.int32)
    # IEEE-style storage: stored 0 flags denormal (effective exponent e_min),
    # stored s in [1, 2^E - 1] is a normal with e = -s ("the minimum value of
    # the exponent is used to represent underflow", paper Sec. V-C).
    exp_stored = jnp.where(is_normal, -e2, 0).astype(jnp.int32)
    return xbar, exp_stored, man


# --------------------------------------------------------------------------
# MLS tensor container
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MLSTensor:
    """A tensor in the multi-level-scaling format (paper Eq. 2).

    ``x = sign * s_t * broadcast(s_g) * xbar`` where ``xbar`` carries the
    ``<Ex,Mx>`` element values (stored both dequantized and as exact
    exponent/mantissa integer fields for the bit-exact kernels).
    """

    sign: jax.Array  # int8, +-1 (0 for zero elements)
    s_t: jax.Array  # f32 scalar tensor-wise scale
    s_g: jax.Array  # f32, group shape (dequantized group scales)
    exp_g: jax.Array  # int32, group shape (stored exponent, >= 0)
    man_g: jax.Array  # int32, group shape
    xbar: jax.Array  # f32, full shape, on-grid magnitudes in [0, 1)
    exp_x: jax.Array  # int32, full shape (stored exponent, >= 0)
    man_x: jax.Array  # int32, full shape
    fmt: EMFormat = dataclasses.field(metadata={"static": True})
    gs_fmt: EMFormat = dataclasses.field(metadata={"static": True})
    spec: GroupSpec = dataclasses.field(metadata={"static": True})

    def tree_flatten(self):
        children = (
            self.sign, self.s_t, self.s_g, self.exp_g, self.man_g,
            self.xbar, self.exp_x, self.man_x,
        )
        return children, (self.fmt, self.gs_fmt, self.spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def shape(self):
        return self.xbar.shape

    def dequant(self) -> jax.Array:
        scale = self.s_t * broadcast_groups(self.s_g, self.spec, self.shape)
        return self.sign.astype(jnp.float32) * scale * self.xbar

    def unit_value(self) -> jax.Array:
        """Dequantized value with the tensor scale ``s_t`` factored out.

        ``sign * s_g * xbar`` has at most ``(Mg+1)+(Mx+1)`` mantissa bits, so
        for the paper's formats it is *exactly* representable in bf16 — this
        is what the low-bit GEMMs consume on the MXU (paper Sec. V-B: the
        tensor-wise scale is applied once to the GEMM output, not per MAC).
        """
        scale = broadcast_groups(self.s_g, self.spec, self.shape)
        return self.sign.astype(jnp.float32) * scale * self.xbar

    def frac_int(self) -> jax.Array:
        """Integer fraction F such that ``xbar = F * 2^(e_min - M)``.

        ``F = (2^M + man) << (2^E - 1 - exp_stored)`` for normals (stored
        exponent in [1, 2^E-1]), ``F = man`` for denormals (stored 0).  This
        is the integer the paper's adder tree multiplies and accumulates
        (Eq. 7); its width is ``M + 2^E - 1`` bits.
        """
        fmt = self.fmt
        top = 2**fmt.e - 1
        is_denorm = self.exp_x == 0
        base = jnp.where(is_denorm, self.man_x, 2**fmt.m + self.man_x)
        shift = jnp.where(is_denorm, 0, top - self.exp_x)
        return base << shift


def mls_quantize(
    x: jax.Array,
    fmt: EMFormat,
    spec: GroupSpec | None = None,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    key: jax.Array | None = None,
) -> MLSTensor:
    """Full dynamic quantization, paper Alg. 2."""
    x = x.astype(jnp.float32)
    if spec is None:
        spec = GroupSpec.per_tensor(x.ndim)
    sign = jnp.sign(x).astype(jnp.int8)
    absx = jnp.abs(x)
    s_r = group_reduce_max(absx, spec)  # group maxima
    s_t = jnp.max(s_r)  # tensor scale
    s_t_safe = jnp.where(s_t > 0, s_t, 1.0)
    s_gf = s_r / s_t_safe
    s_g, exp_g, man_g = quantize_group_scale(s_gf, gs_fmt)
    denom = s_t_safe * broadcast_groups(s_g, spec, x.shape)
    x_f = jnp.where(denom > 0, absx / jnp.where(denom > 0, denom, 1.0), 0.0)
    r = srandom_like(key, x) if key is not None else None
    xbar, exp_x, man_x = quantize_elements(x_f, fmt, r)
    return MLSTensor(
        sign=sign, s_t=s_t_safe, s_g=s_g, exp_g=exp_g, man_g=man_g,
        xbar=xbar, exp_x=exp_x, man_x=man_x, fmt=fmt, gs_fmt=gs_fmt, spec=spec,
    )


def fake_quant(
    x: jax.Array,
    fmt: EMFormat,
    spec: GroupSpec | None = None,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    key: jax.Array | None = None,
) -> jax.Array:
    """Quantize-dequantize: returns an fp32 tensor exactly on the MLS grid."""
    return mls_quantize(x, fmt, spec, gs_fmt, key).dequant()


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def fake_quant_ste(x, fmt, spec, gs_fmt, key=None):
    return fake_quant(x, fmt, spec, gs_fmt, key)


def _fq_fwd(x, fmt, spec, gs_fmt, key=None):
    return fake_quant(x, fmt, spec, gs_fmt, key), None


def _fq_bwd(fmt, spec, gs_fmt, res, g):
    return (g, None)


fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)


# --------------------------------------------------------------------------
# Packed int8 codec (for quantized storage / collective compression)
# --------------------------------------------------------------------------
def pack_elements(t: MLSTensor) -> jax.Array:
    """Pack sign/exp/man into uint8 codes: [sign | exp | man] (<= 8 bits)."""
    fmt = t.fmt
    if fmt.element_bits > 8:
        raise ValueError(f"{fmt} does not fit in 8 bits")
    sign_bit = (t.sign.astype(jnp.int32) < 0).astype(jnp.int32)
    code = (sign_bit << (fmt.e + fmt.m)) | (t.exp_x << fmt.m) | t.man_x
    return code.astype(jnp.uint8)


def unpack_elements(code: jax.Array, fmt: EMFormat):
    """Inverse of :func:`pack_elements` -> (sign, xbar) dequantized fields."""
    code = code.astype(jnp.int32)
    man = code & (2**fmt.m - 1)
    exp = (code >> fmt.m) & (2**fmt.e - 1)
    sign_bit = code >> (fmt.e + fmt.m)
    top = 2**fmt.e - 1
    is_denorm = exp == 0
    frac = jnp.where(is_denorm, 0.0, 1.0) + man.astype(jnp.float32) * 2.0**-fmt.m
    mag = frac * pow2(-jnp.where(is_denorm, top, exp))
    sign = 1.0 - 2.0 * sign_bit.astype(jnp.float32)
    # zero has man==0, exp==0 (denormal) -> mag 0; sign bit irrelevant
    return sign, mag


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------
def average_relative_error(x: jax.Array, q: jax.Array) -> jax.Array:
    """ARE used in the paper's Fig. 7 / Table IV analysis:
    mean(|x - q|) / mean(|x|)."""
    return jnp.mean(jnp.abs(x - q)) / jnp.maximum(jnp.mean(jnp.abs(x)), 1e-30)
