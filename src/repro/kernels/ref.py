"""Pure-jnp oracles for the Pallas kernels.

These mirror the kernels' *quantized-domain* semantics exactly (integer
fractions, group scales, tensor scale factored out), so kernel-vs-ref tests
can assert bit-identical results, and they are cross-checked against the
float `repro.core` implementation in the test suite.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.formats import EMFormat, GS_FMT_DEFAULT
from repro.core.quantize import (
    GroupSpec,
    mls_quantize,
    pack_elements,
    unpack_elements,
)


def grouping_spec(grouping: str, k_block: int) -> GroupSpec:
    """GroupSpec of a 2-D (rows, contraction) operand for one grouping."""
    if grouping == "nc":
        return GroupSpec((1, k_block))
    if grouping == "c":
        return GroupSpec((None, k_block))
    if grouping == "n":
        return GroupSpec((1, None))
    if grouping == "none":
        return GroupSpec((None, None))
    raise ValueError(f"unknown grouping {grouping!r}")


def quantize_ref(
    x: jax.Array,
    fmt: EMFormat,
    k_block: int,
    gs_fmt: EMFormat = GS_FMT_DEFAULT,
    r_u8: jax.Array | None = None,
    grouping: str = "nc",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reference dynamic quantization of a 2-D operand ``(M, K)``.

    Scaling groups follow ``grouping`` (default ``"nc"``: one group per
    (row, k-block)).  ``r_u8`` is the uint8 stochastic-rounding source the
    kernel consumes (``None`` -> round-to-nearest).  Returns ``(codes_u8,
    s_g_f32, s_t_f32_scalar)`` with ``codes`` the packed sign/exp/man
    elements and ``s_g`` in the grouping's compact layout (``"nc"``:
    ``(M, K // k_block)``; see ``kernels.mls_matmul.sg_shapes``).
    """
    assert x.ndim == 2
    if grouping in ("nc", "c"):
        assert x.shape[1] % k_block == 0
    if r_u8 is not None:
        # mirror the kernel: u = (r + 0.5)/256 - 0.5 in (-0.5, 0.5)
        r = (r_u8.astype(jnp.float32) + 0.5) / 256.0 - 0.5
    else:
        r = None
    spec = grouping_spec(grouping, k_block)
    # re-implement mls_quantize but with the supplied rounding tensor
    from repro.core.quantize import (
        broadcast_groups,
        group_reduce_max,
        quantize_elements,
        quantize_group_scale,
    )

    xf32 = x.astype(jnp.float32)
    sign = jnp.sign(xf32).astype(jnp.int8)
    absx = jnp.abs(xf32)
    s_r = group_reduce_max(absx, spec)
    s_t = jnp.max(s_r)
    s_t = jnp.where(s_t > 0, s_t, 1.0)
    s_g, _, _ = quantize_group_scale(s_r / s_t, gs_fmt)
    denom = s_t * broadcast_groups(s_g, spec, x.shape)
    x_f = jnp.where(denom > 0, absx / jnp.where(denom > 0, denom, 1.0), 0.0)
    xbar, exp_x, man_x = quantize_elements(x_f, fmt, r)
    sign_bit = (sign.astype(jnp.int32) < 0).astype(jnp.int32)
    codes = ((sign_bit << (fmt.e + fmt.m)) | (exp_x << fmt.m) | man_x).astype(
        jnp.uint8
    )
    return codes, s_g, s_t


def decode_frac_int(codes: jax.Array, fmt: EMFormat) -> jax.Array:
    """uint8 codes -> signed integer fractions F (paper Eq. 7 operands).

    ``|value| = |F| * 2^(e_min - M)``; F fits in ``M + 2^E - 1`` magnitude
    bits plus sign.
    """
    c = codes.astype(jnp.int32)
    man = c & (2**fmt.m - 1)
    exp = (c >> fmt.m) & (2**fmt.e - 1)
    sign_bit = c >> (fmt.e + fmt.m)
    top = 2**fmt.e - 1
    is_denorm = exp == 0
    base = jnp.where(is_denorm, man, 2**fmt.m + man)
    shift = jnp.where(is_denorm, 0, top - exp)
    f = base << shift
    return jnp.where(sign_bit == 1, -f, f)


def mls_matmul_ref(
    x_codes: jax.Array,
    x_sg: jax.Array,
    x_st: jax.Array,
    w_codes: jax.Array,
    w_sg: jax.Array,
    w_st: jax.Array,
    fmt: EMFormat,
    k_block: int,
) -> jax.Array:
    """Quantized-domain GEMM oracle (paper Eq. 6-8).

    x: (M, K) codes;  w: (K, N) codes.  The group scales may arrive in any
    compact grouping layout (``sg_shapes``) — they are broadcast to the
    ``"nc"`` resolution (M, K/kb) / (K/kb, N), which subsumes the coarser
    layouts exactly.
    Intra-group: integer MAC over each k-block (exact in fp32).
    Inter-group: group-scale product (a shift-add in hardware, exact fp32
    multiply here) then fp32 accumulation in group order — the paper's
    adder tree.
    """
    M, K = x_codes.shape
    K2, N = w_codes.shape
    assert K == K2 and K % k_block == 0
    nkb = K // k_block
    x_sg = jnp.broadcast_to(x_sg, (M, nkb))
    w_sg = jnp.broadcast_to(w_sg, (nkb, N))
    fx = decode_frac_int(x_codes, fmt).astype(jnp.float32)  # exact small ints
    fw = decode_frac_int(w_codes, fmt).astype(jnp.float32)
    fx = fx.reshape(M, nkb, k_block)
    fw = fw.reshape(nkb, k_block, N)
    # intra-group integer MACs: P[g, m, n] (exact at any precision that
    # keeps fp32 products; HIGHEST so a TPU does not round operands to bf16)
    p = jnp.einsum("mgk,gkn->gmn", fx, fw, precision=jax.lax.Precision.HIGHEST)
    # inter-group: scale by S_p = s_g^x * s_g^w and accumulate group by
    # group in contraction order — the kernel's order, on every backend
    sp = x_sg.T[:, :, None] * w_sg[:, None, :]  # (g, M, N)

    def add_group(z, ps):
        return z + ps[0] * ps[1], None

    z, _ = jax.lax.scan(add_group, jnp.zeros((M, N), jnp.float32), (p, sp))
    unit = 2.0 ** (2 * (fmt.e_min - fmt.m))
    return z * (x_st * w_st * unit)
