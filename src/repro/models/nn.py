"""Minimal functional NN library (no flax dependency).

Parameters are plain pytrees of ``jnp`` arrays; every layer is an
``init_*(key, ...) -> params`` / ``apply(params, x, ...) -> y`` pair.  Layers
that contain a GEMM/conv take an optional :class:`repro.core.QuantConfig`;
when given (and enabled) the op runs through the paper's low-bit training
path (quantized W/A/E with STE), otherwise through a plain fp32/bf16 op.

Stochastic-rounding keys: callers pass one per-step key; layers fold in a
stable integer tag so every quantization site gets an independent stream
(the paper generates its U[-1/2,1/2) tensors offline — same semantics).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, lowbit_conv, lowbit_matmul

Array = jax.Array

# ---------------------------------------------------------------------------
# op tracing (for the paper's Table I / Table VI op-count analyses)
# ---------------------------------------------------------------------------
_OP_TRACE: list | None = None


class OpTrace:
    """Context manager that records (op, dims) for every conv/linear/bn/add
    executed inside — run the model under ``jax.eval_shape`` to collect the
    exact per-layer op counts the paper tabulates."""

    def __enter__(self):
        global _OP_TRACE
        self._prev, _OP_TRACE = _OP_TRACE, []
        return self

    def __exit__(self, *exc):
        global _OP_TRACE
        self.ops, _OP_TRACE = _OP_TRACE, self._prev
        return False


def _trace(kind: str, **dims):
    if _OP_TRACE is not None:
        _OP_TRACE.append((kind, dims))


def ew_add(a: Array, b: Array) -> Array:
    """Element-wise residual add (traced: paper Table I counts these)."""
    _trace("ew_add", numel=int(jnp.size(a)))
    with jax.named_scope("act"):
        return a + b


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def kaiming(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def xavier(key, shape, fan_in, fan_out, dtype=jnp.float32):
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def trunc_normal(key, shape, std=0.02, dtype=jnp.float32):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * std


# ---------------------------------------------------------------------------
# linear / conv with optional MLS quantization
# ---------------------------------------------------------------------------
def init_linear(key, d_in, d_out, bias=False, dtype=jnp.float32, std=None):
    kw, kb = jax.random.split(key)
    w = (
        trunc_normal(kw, (d_in, d_out), std, dtype)
        if std is not None
        else xavier(kw, (d_in, d_out), d_in, d_out, dtype)
    )
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x, qcfg: QuantConfig | None = None, key=None, wire=None):
    """x: (..., d_in) @ w (d_in, d_out); bias (if any) added in fp32.

    ``wire``: which weight dim is FSDP-sharded (pins the FSDP gather onto
    the quantized low-precision values — §Perf; None disables)."""
    _trace(
        "fc",
        d_in=p["w"].shape[0],
        d_out=p["w"].shape[1],
        rows=int(jnp.size(x) // x.shape[-1]),
        quantized=qcfg is not None and qcfg.enabled,
    )
    with jax.named_scope("fc"):
        if qcfg is not None and qcfg.enabled:
            if qcfg.backend == "pallas":
                from repro.kernels import lowbit_matmul_qd

                # quantized-domain path: the FSDP wire pinning is a fake-quant
                # concern (the Pallas path already moves 1-byte codes).  The
                # kernels honor qcfg.grouping / block_m / block_n — unset
                # blocks resolve per-shape through the autotuner cache.
                y = lowbit_matmul_qd(x, p["w"].astype(jnp.float32), key, qcfg)
            else:
                if wire is not None and qcfg.wire_fsdp_dim != wire:
                    import dataclasses as _dc

                    qcfg = _dc.replace(qcfg, wire_fsdp_dim=wire)
                y = lowbit_matmul(x, p["w"].astype(jnp.float32), key, qcfg)
        else:
            dt = x.dtype
            y = jax.lax.dot_general(
                x, p["w"].astype(dt),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return y


def init_conv(key, c_in, c_out, ksize, dtype=jnp.float32):
    fan_in = c_in * ksize * ksize
    return {"w": kaiming(key, (c_out, c_in, ksize, ksize), fan_in, dtype)}


def conv2d(p, x, stride=1, padding="SAME", qcfg: QuantConfig | None = None, key=None):
    """NCHW conv; quantized per paper Alg. 1 when qcfg is given."""
    s = (stride, stride) if isinstance(stride, int) else stride
    co, ci, kh, kw = p["w"].shape
    _trace(
        "conv",
        c_in=ci, c_out=co, k=kh,
        h=x.shape[2] // s[0], w=x.shape[3] // s[1], n=x.shape[0],
        quantized=qcfg is not None and qcfg.enabled,
    )
    with jax.named_scope("conv"):
        if qcfg is not None and qcfg.enabled:
            if qcfg.backend == "pallas":
                from repro.kernels import lowbit_conv_fused

                return lowbit_conv_fused(x, p["w"], key, s, padding, qcfg)
            return lowbit_conv(x, p["w"], key, s, padding, qcfg)
        return jax.lax.conv_general_dilated(
            x, p["w"].astype(x.dtype), s, padding,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            preferred_element_type=jnp.float32,
        )


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
def init_batchnorm(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}


def batchnorm(p, x, eps=5e-5):
    """Training-mode BN over (N, H, W) of NCHW, fp32 (paper keeps BN full
    precision; eps matches paper Eq. 13)."""
    _trace("bn", numel=int(jnp.size(x)))
    with jax.named_scope("bn"):
        x = x.astype(jnp.float32)
        mu = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x), axis=(0, 2, 3), keepdims=True) - jnp.square(mu)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        return y * p["gamma"][None, :, None, None] + p["beta"][None, :, None, None]


def init_layernorm(d):
    return {"gamma": jnp.ones((d,)), "beta": jnp.zeros((d,))}


def layernorm(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["gamma"] + p["beta"]).astype(x.dtype)


def init_rmsnorm(d):
    return {"gamma": jnp.ones((d,))}


def rmsnorm(p, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * p["gamma"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope_angles(positions: Array, head_dim: int, theta: float = 10000.0,
                rotary_dim: int | None = None):
    """Returns (sin, cos) of shape (..., rotary_dim/2)."""
    rd = rotary_dim or head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: Array, sin: Array, cos: Array, rotary_dim: int | None = None):
    """x: (B, S, H, D). Rotates the first ``rotary_dim`` dims (half-rotary
    style used by GLM when rotary_dim < D)."""
    d = x.shape[-1]
    rd = rotary_dim or d
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1) if rd < d else out


# ---------------------------------------------------------------------------
# attention (GQA, causal, optional sliding window, optional KV cache)
# ---------------------------------------------------------------------------
def _gqa_attention_block(q, k, v, causal, q_offset, window, kv_len):
    b, sq, hkv, g, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    if kv_len is not None:
        mask = mask & (kpos < kv_len)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))


def gqa_attention(
    q: Array,  # (B, Sq, Hq, D)
    k: Array,  # (B, Sk, Hkv, D)
    v: Array,  # (B, Sk, Hkv, D)
    causal: bool = True,
    q_offset: Array | int = 0,  # position of q[0] within the kv sequence
    window: int | None = None,  # sliding-window size (None = full)
    kv_len: Array | None = None,  # number of valid cache slots
    q_chunk: int | None = None,  # memory-efficient query chunking
):
    """Grouped-query attention.  With ``q_chunk`` the query axis is scanned
    in blocks (exact softmax per block over the full key range) so the score
    matrix never exceeds (B, H, q_chunk, Sk) — required for 32k+ prefill."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    if q_chunk is None or sq <= q_chunk or sq % q_chunk != 0:
        out = _gqa_attention_block(qg, k, v, causal, q_offset, window, kv_len)
        return out.reshape(b, sq, hq, d)

    nq = sq // q_chunk
    qb = qg.reshape(b, nq, q_chunk, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)

    def body(_, inp):
        qi, i = inp
        off = q_offset + i * q_chunk
        return None, _gqa_attention_block(qi, k, v, causal, off, window, kv_len)

    _, out = jax.lax.scan(body, None, (qb, jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, d)
    return out
