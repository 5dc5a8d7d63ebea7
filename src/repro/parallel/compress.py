"""MLS-compressed cross-pod gradient all-reduce (beyond-paper application of
the paper's format as a distributed-training compressor).

Within a pod, gradients all-reduce in full precision over fast ICI.  Across
pods the link is slow DCN, so each pod quantizes its pod-local gradient to
packed MLS codes (1 byte/element + one ``<8,1>`` scale per 128-group + one
fp32 scale/tensor ≈ **4x fewer wire bytes than fp32**, 2x vs bf16), exchanges
with ``collective_permute``, dequantizes and averages.  Stochastic rounding
keeps the compression unbiased (the same property the paper relies on for
SGD convergence, Sec. II-C).

For >2 pods the exchange generalizes to a ring of permutes (log or linear);
this module implements the 2-pod case used by the production mesh and the
generic ring for p pods.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EMFormat, FMT_IMAGENET, GS_FMT_DEFAULT
from repro.core.quantize import GroupSpec, mls_quantize, pack_elements, unpack_elements


def _flatten_pad(g: jax.Array, block: int):
    flat = g.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block)


def compress(g: jax.Array, fmt: EMFormat = FMT_IMAGENET, block: int = 128,
             key: jax.Array | None = None):
    """-> (codes uint8 (n, block), s_g f32 (n, 1), s_t f32 scalar)."""
    rows = _flatten_pad(g, block)
    t = mls_quantize(rows, fmt, GroupSpec((1, block)), GS_FMT_DEFAULT, key)
    return pack_elements(t), t.s_g, t.s_t


def decompress(codes, s_g, s_t, shape, fmt: EMFormat = FMT_IMAGENET):
    sign, mag = unpack_elements(codes, fmt)
    vals = sign * mag * s_g * s_t
    return vals.reshape(-1)[: int(np.prod(shape))].reshape(shape)


def crosspod_allreduce_mean(g: jax.Array, axis_name: str = "pod",
                            fmt: EMFormat = FMT_IMAGENET,
                            key: jax.Array | None = None) -> jax.Array:
    """Mean over the pod axis exchanging MLS-compressed codes.

    Must run inside ``shard_map`` with ``axis_name`` bound.  Exact wire
    payload per hop: 1 B/elem codes + 4 B/128-elem group scales.
    """
    p = int(jax.lax.axis_size(axis_name))
    if p == 1:
        return g
    codes, s_g, s_t = compress(g, fmt, key=key)
    acc = g.astype(jnp.float32)
    perm_fwd = [(i, (i + 1) % p) for i in range(p)]
    my_codes, my_sg, my_st = codes, s_g, s_t
    for _ in range(p - 1):  # ring: p-1 hops of compressed payloads
        my_codes = jax.lax.ppermute(my_codes, axis_name, perm_fwd)
        my_sg = jax.lax.ppermute(my_sg, axis_name, perm_fwd)
        my_st = jax.lax.ppermute(my_st, axis_name, perm_fwd)
        acc = acc + decompress(my_codes, my_sg, my_st, g.shape, fmt)
    return (acc / p).astype(g.dtype)
