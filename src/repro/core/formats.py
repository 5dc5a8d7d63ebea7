"""Custom ``<E,M>`` floating-point format math (paper Sec. IV-A, V-C).

A value in the (unsigned) ``<E,M>`` format is

    normal   : (1 + Man/2^M) * 2^e      e in [e_min, -1],  Man in [0, 2^M)
    denormal : (    Man/2^M) * 2^e_min  (gradual underflow, IEEE-754 style)

with ``e_min = 1 - 2^E``.  The exponent is stored as ``-e`` in E bits; the
stored maximum (``-e = 2^E - 1``, i.e. the minimum float magnitude level)
doubles as the denormal level, exactly as described in paper Sec. V-C.
All representable magnitudes lie in ``[0, (2 - 2^-M) * 2^-1] ⊂ [0, 1)``.

The same math implements the group-scale format ``<Eg,Mg>`` (Mg ∈ {0,1}) —
there the fraction is *ceil*-rounded and the value may be exactly 1
(exponent clipped to 0), see :func:`repro.core.quantize.quantize_group_scale`.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "EMFormat",
    "FMT_CIFAR",
    "FMT_IMAGENET",
    "GS_FMT_DEFAULT",
    "accumulation_bits",
    "exponent_fraction",
    "pow2",
    "srandom_like",
]


@dataclasses.dataclass(frozen=True)
class EMFormat:
    """Bit layout of a ``<E,M>`` unsigned low-bit float."""

    e: int  # exponent bits
    m: int  # mantissa bits

    def __post_init__(self):
        if self.e < 0 or self.m < 0 or (self.e == 0 and self.m == 0):
            raise ValueError(f"invalid <E,M> format <{self.e},{self.m}>")

    # ---- derived constants -------------------------------------------------
    @property
    def e_min(self) -> int:
        """Most negative normal exponent (== denormal exponent).

        E == 0 is plain fixed point (paper Table II "single number"
        bit-widths): no exponent field, no implicit leading 1 — the grid is
        ``man/2^M`` with step ``2^-M`` over [0, 1)."""
        return 1 - 2**self.e if self.e > 0 else 0

    @property
    def max_value(self) -> float:
        """Largest representable magnitude."""
        if self.e == 0:
            return (2.0**self.m - 1.0) / 2.0**self.m
        return (2.0 - 2.0 ** (-self.m)) * 0.5

    @property
    def min_normal(self) -> float:
        return 2.0**self.e_min

    @property
    def min_subnormal(self) -> float:
        return 2.0 ** (self.e_min - self.m)

    @property
    def element_bits(self) -> int:
        """Storage bits per signed element (sign + exponent + mantissa)."""
        return 1 + self.e + self.m

    @property
    def product_bits(self) -> int:
        """Integer bit-width of a product of two <E,M> values (paper §V-C):
        ``2M + 2^(E+1) - 2`` bits."""
        return 2 * self.m + 2 ** (self.e + 1) - 2

    @property
    def max_fraction(self) -> int:
        """Largest |integer fraction| of a decoded code.

        The quantized-domain GEMM contracts codes as exact integers
        ``F`` with ``|value| = |F| * 2^(e_min - M)`` (``kernels/ref.py``
        ``decode_frac_int``); the largest magnitude is the top normal:
        ``(2^(M+1) - 1) << (2^E - 2)``.  ``max_fraction^2`` spans exactly
        ``product_bits`` bits — the closed form the static interval prover
        (``analysis/intervals.py``) must reproduce from the kernel jaxpr.
        """
        if self.e == 0:
            return 2**self.m - 1
        return (2 ** (self.m + 1) - 1) << (2**self.e - 2)

    def fraction_bound(self) -> tuple[int, int]:
        """``(lo, hi)`` interval of decoded signed integer fractions — the
        operand seed for interval-domain kernel verification."""
        return -self.max_fraction, self.max_fraction

    def grid(self) -> np.ndarray:
        """All representable non-negative values, ascending (for tests)."""
        vals = {0.0}
        for man in range(2**self.m):  # denormals (all values for E == 0)
            vals.add((man / 2**self.m) * 2.0**self.e_min)
        n_exp_levels = 2**self.e - 1 if self.e > 0 else 0
        for k in range(n_exp_levels):  # normals: e = e_min + k .. -1
            e = self.e_min + k
            for man in range(2**self.m):
                vals.add((1 + man / 2**self.m) * 2.0**e)
        return np.array(sorted(vals))

    def __str__(self) -> str:  # matches the paper's ⟨E,M⟩ notation
        return f"<{self.e},{self.m}>"


def accumulation_bits(fmt: EMFormat, k_block: int) -> int:
    """Integer bits spanned by a sum of ``k_block`` products of two ``fmt``
    values: ``product_bits + ceil(log2(k_block))``.  The quantized-domain
    GEMM accumulates in fp32, which is bit-exact only while this stays
    below 24 (see ``kernels/mls_matmul.py``)."""
    if k_block < 1:
        raise ValueError(f"k_block must be >= 1, got {k_block}")
    return fmt.product_bits + math.ceil(math.log2(k_block))


# Paper's headline configurations (Table II).
FMT_CIFAR = EMFormat(e=2, m=1)  # <2,1>: 1-bit mantissa, 2-bit exponent
FMT_IMAGENET = EMFormat(e=2, m=4)  # <2,4>: 4-bit mantissa, 2-bit exponent
GS_FMT_DEFAULT = EMFormat(e=8, m=1)  # group scale <8,1> (paper Table II note)


def exponent_fraction(x: jax.Array):
    """``Exponent``/``Fraction`` of paper Alg. 2: x = frac * 2^e, frac∈[1,2).

    Uses exact bit manipulation of the float32 representation (no log2), so
    results are exact for all finite positive inputs.  x == 0 maps to
    (e=INT32_MIN/2, frac=0) which downstream clipping turns into zero.
    """
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    raw_exp = (bits >> 23) & 0xFF
    man_bits = bits & 0x7FFFFF
    is_zero = x == 0.0
    # fp32 subnormal inputs: treat as zero (they are < 2^-126, far below any
    # <E,M> grid after scaling; scales are maxima so never subnormal).
    is_sub = raw_exp == 0
    e = raw_exp - 127
    frac = jax.lax.bitcast_convert_type(
        jnp.where(is_sub, 0, man_bits) | (127 << 23), jnp.int32
    )
    frac = jax.lax.bitcast_convert_type(frac, jnp.float32)
    e = jnp.where(is_zero | is_sub, jnp.int32(-(2**30)), e)
    frac = jnp.where(is_zero | is_sub, 0.0, frac)
    return e, frac


def pow2(e: jax.Array) -> jax.Array:
    """Exact ``2.0 ** e`` in fp32 for integer ``e`` in the normal range
    [-126, 127], built from the exponent field.  ``jnp.exp2`` is not exact
    on every backend (XLA's CPU exp2 is off by ulps below 2^-13), and MLS
    scales and grid steps must be true powers of two."""
    e = jnp.asarray(e).astype(jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def srandom_like(key: jax.Array, x: jax.Array) -> jax.Array:
    """U[-1/2, 1/2) tensor for stochastic rounding (paper Eq. 5)."""
    return jax.random.uniform(key, x.shape, jnp.float32, -0.5, 0.5)
