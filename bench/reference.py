"""Plain reference of one training step of the benchmark's CNNs.

Written from the published descriptions, in straightforward ``jax.numpy``
at float32 and ``Precision.HIGHEST``; it imports nothing of the program.

* The network: ResNet basic blocks (He et al. 2016), training-mode batch
  norm, global average pooling, a float classifier, softmax cross-entropy,
  and SGD with momentum and weight decay.
* ``numerics == "mls"``: every convolution but the stem runs the MLS
  low-bit arithmetic of Zhong et al. 2020 (Alg. 1 and 2) on the im2col form
  of the convolution.  Each of its three GEMMs (forward, weight gradient,
  input gradient) quantizes both operands afresh: one group per row and per
  ``k_block`` elements of the GEMM's contraction axis (the contraction of a
  convolution's patch matrix runs over (channel, kernel row, kernel
  column)), a tensor scale that is the operand's largest magnitude, group
  scales rounded up onto the ``<Eg,Mg>`` grid, and elements rounded onto the
  ``<E,M>`` grid with stochastic rounding (noise uniform in [-1/2, 1/2) of
  a grid step, drawn here from the reference's own key).  The quantized
  values are multiplied back out and the GEMM runs in float32.
* ``operand`` / ``cotangent``: where set, the inputs of every GEMM that
  the configuration keeps in float (forward operands, and the incoming
  gradient of the backward GEMMs) are rounded to that format first
  (``FORMATS``), and the products are summed in float32.  The cells state
  XLA's default precision for these GEMMs, which on a TPU rounds each
  operand to bfloat16 (``XLA_DEFAULT``); the plain cells' control rounds
  to 8-bit floats with a per-tensor scale.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from bench.model import convs

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Numerics:
    """What the reference computes in (hashable: a static jit argument)."""

    fmt: tuple[int, int] | None = None  # MLS <E,M> of the quantized convs
    gs_fmt: tuple[int, int] = (8, 1)  # <Eg,Mg> of the group scales
    k_block: int = 128
    stochastic: bool = True
    operand: str | None = None  # a FORMATS name; None: float32 as it is
    cotangent: str | None = None


# the format XLA's default precision rounds a float32 GEMM's operands to,
# by platform (none: float32 as it is)
XLA_DEFAULT = {"tpu": "bfloat16"}

# significant bits, smallest normal exponent, largest value (a per-tensor
# scale maps the tensor's largest magnitude onto it)
FORMATS = {
    "bfloat16": (8, None, None),
    "e4m3": (4, -6, 448.0),
    "e5m2": (3, -14, 57344.0),
}


# ---------------------------------------------------------------------------
# exact powers of two and rounding
# ---------------------------------------------------------------------------
def pow2(e: jax.Array) -> jax.Array:
    """``2.0 ** e`` for integer ``e`` in [-126, 127], exact."""
    e = jnp.asarray(e, jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def floor_log2(x: jax.Array) -> jax.Array:
    """``floor(log2(x))`` of positive normal float32 values (from the
    exponent field); 0 maps to -127."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def round_significant(x: jax.Array, bits: int, min_exp: int | None,
                      max_value: float | None = None) -> jax.Array:
    """Round to nearest with ``bits`` significant bits; below ``2**min_exp``
    the grid step stays that of ``min_exp`` (gradual underflow).  With
    ``max_value`` the tensor is first scaled so its largest magnitude is
    ``max_value`` (a per-tensor scale, as 8-bit float training uses)."""
    x = x.astype(jnp.float32)
    scale = jnp.float32(1.0)
    if max_value is not None:
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / max_value, 1.0)
    y = jnp.abs(x) / scale
    e = floor_log2(y)
    if min_exp is not None:
        e = jnp.maximum(e, min_exp)
    step = pow2(jnp.clip(e - (bits - 1), -126, 127))
    q = jnp.round(y / step) * step
    return jnp.sign(x) * q * scale


# ---------------------------------------------------------------------------
# MLS quantization of a GEMM operand (paper Alg. 2)
# ---------------------------------------------------------------------------
def _group_scale(r: jax.Array, eg: int, mg: int) -> jax.Array:
    """Smallest value of the ``<eg,mg>`` scale grid that is >= ``r``
    (``r`` in [0, 1]); ratios below the grid's least scale take that."""
    e_least = max(1 - 2**eg, -120)
    e = floor_log2(r)
    frac = r / pow2(jnp.clip(e, -126, 127))  # in [1, 2)
    man = jnp.ceil((frac - 1.0) * 2.0**mg)
    carry = man >= 2.0**mg
    man = jnp.where(carry, 0.0, man)
    e = jnp.minimum(jnp.where(carry, e + 1, e), 0)
    s = (1.0 + man * 2.0**-mg) * pow2(jnp.clip(e, -126, 0))
    return jnp.where((r <= 0) | (e < e_least), pow2(e_least), s)


def _element(xf: jax.Array, ee: int, mm: int, u) -> jax.Array:
    """Round magnitudes in [0, 1] onto the ``<ee,mm>`` grid: exponents from
    ``1 - 2**ee`` (denormal below) to -1, ``mm`` mantissa bits, saturating
    at ``(2 - 2**-mm) / 2``."""
    e = jnp.clip(floor_log2(xf), 1 - 2**ee, -1)
    step = pow2(e - mm)
    q = jnp.floor(xf / step + u + 0.5)
    top = jnp.where(e == -1, 2.0 ** (mm + 1) - 1.0, 2.0 ** (mm + 1))
    return jnp.clip(q, 0.0, top) * step


def mls_rows(x: jax.Array, num: Numerics, key) -> jax.Array:
    """MLS-quantize a 2-D GEMM operand whose contraction is axis 1, and
    return the quantized values in float32."""
    rows, k = x.shape
    kb = num.k_block
    kp = -(-k // kb) * kb
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, kp - k)))
    a = jnp.abs(xp).reshape(rows, kp // kb, kb)
    gmax = jnp.max(a, axis=-1)
    st = jnp.max(gmax)
    st = jnp.where(st > 0, st, 1.0)
    sg = _group_scale(gmax / st, *num.gs_fmt)
    xf = a / (st * sg)[..., None]
    u = (jax.random.uniform(key, a.shape, jnp.float32, -0.5, 0.5)
         if num.stochastic else 0.0)
    q = _element(xf, *num.fmt, u) * sg[..., None] * st
    return (jnp.sign(xp) * q.reshape(rows, kp))[:, :k]


# ---------------------------------------------------------------------------
# convolutions
# ---------------------------------------------------------------------------
def _same(h: int, k: int, s: int) -> tuple[int, int, int]:
    out = -(-h // s)
    total = max((out - 1) * s + k - h, 0)
    return out, total // 2, total - total // 2


def im2col(x: jax.Array, k: int, s: int) -> jax.Array:
    """NCHW -> (N*OH*OW, C*k*k) patches, columns in (c, kh, kw) order."""
    n, c, h, w = x.shape
    oh, pt, pb = _same(h, k, s)
    ow, pl, pr = _same(w, k, s)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    taps = [xp[:, :, i:i + s * (oh - 1) + 1:s, j:j + s * (ow - 1) + 1:s]
            for i in range(k) for j in range(k)]
    t = jnp.stack(taps, axis=2)  # (N, C, k*k, OH, OW)
    return t.transpose(0, 3, 4, 1, 2).reshape(n * oh * ow, c * k * k)


def _mm(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def mls_conv(x, w, key, stride: int, num: Numerics):
    """Convolution with MLS-quantized forward, weight- and input-gradient
    GEMMs (paper Alg. 1; straight-through for the quantizers)."""
    return _mls_conv_fwd(x, w, key, stride, num)[0]


def _mls_conv_fwd(x, w, key, stride, num):
    o, c, k, _ = w.shape
    n, oh = x.shape[0], -(-x.shape[2] // stride)
    ks = jax.random.split(key, 6)
    cols = im2col(x, k, stride)
    wmat = w.reshape(o, c * k * k)
    y = _mm(mls_rows(cols, num, ks[0]), mls_rows(wmat, num, ks[1]).T)
    return y.reshape(n, oh, oh, o).transpose(0, 3, 1, 2), (x, w, key)


def _mls_conv_bwd(stride, num, res, g):
    x, w, key = res
    o, c, k, _ = w.shape
    ks = jax.random.split(key, 6)
    cols, unpatch = jax.vjp(lambda a: im2col(a, k, stride), x)
    e = g.transpose(0, 2, 3, 1).reshape(-1, o)
    wmat = w.reshape(o, c * k * k)
    dw = _mm(mls_rows(cols.T, num, ks[2]), mls_rows(e.T, num, ks[3]).T)
    dcols = _mm(mls_rows(e, num, ks[4]), mls_rows(wmat.T, num, ks[5]).T)
    (dx,) = unpatch(dcols)
    return dx, dw.T.reshape(w.shape), None


mls_conv.defvjp(_mls_conv_fwd, _mls_conv_bwd)


def _round(t, name: str | None):
    if name is None:
        return t
    return round_significant(t, *FORMATS[name])


def _rounded(fn):
    """``fn(a, b)`` at HIGHEST, its operands rounded to ``num.operand`` and
    the incoming gradient of its backward to ``num.cotangent``."""

    @partial(jax.custom_vjp, nondiff_argnums=(2,))
    def op(a, b, num):
        return fn(_round(a, num.operand), _round(b, num.operand))

    def fwd(a, b, num):
        return op(a, b, num), (a, b)

    def bwd(num, res, g):
        a, b = res
        _, vjp = jax.vjp(fn, _round(a, num.operand), _round(b, num.operand))
        return vjp(_round(g, num.cotangent))

    op.defvjp(fwd, bwd)
    return op


def _conv_f32(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


_float_conv = {s: _rounded(partial(_conv_f32, stride=s)) for s in (1, 2)}
_float_dense = _rounded(_mm)


# ---------------------------------------------------------------------------
# the network and its training step
# ---------------------------------------------------------------------------
def _batchnorm(p, x, eps):
    mu = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.var(x, axis=(0, 2, 3), keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["gamma"][None, :, None, None] + p["beta"][None, :, None, None]


def _conv(x, w, key, stride, quantized, num):
    if quantized and num.fmt is not None:
        return mls_conv(x, w, key, stride, num)
    return _float_conv[stride](x, w, num)


def _stem(images, w, bn, key, *, stride, pool, num, eps):
    """The float first conv, its batch norm, ReLU (and max pool)."""
    h = jax.nn.relu(_batchnorm(bn, _conv(images, w, key, stride, False, num),
                               eps))
    if pool:
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                                  (1, 1, 2, 2), "SAME")
    return h


def _block(x, p, key, *, stride, num, eps):
    """A basic block: two 3x3 convs, a 1x1 projection where the shape
    changes, each with batch norm; ReLU after the sum."""
    k = [jax.random.fold_in(key, j) for j in range(3)]
    h = jax.nn.relu(_batchnorm(p["bn1"], _conv(x, p["conv1"]["w"], k[0],
                                                stride, True, num), eps))
    h = _batchnorm(p["bn2"], _conv(h, p["conv2"]["w"], k[1], 1, True, num),
                   eps)
    if "proj" in p:
        x = _batchnorm(p["bn_proj"], _conv(x, p["proj"]["w"], k[2], stride,
                                           True, num), eps)
    return jax.nn.relu(h + x)


def _head(h, fc, labels, num):
    """Global average pool, the float classifier, mean cross-entropy."""
    logits = _float_dense(jnp.mean(h, axis=(2, 3)), fc["w"], num) + fc["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


# The step runs piece by piece: each piece's forward, and its backward
# (which recomputes its forward), is a program of its own, compiled once
# per shape.  The whole step as one program takes the TPU compiler many
# minutes; pieces also keep only the blocks' inputs alive.
_STEM = ("stride", "pool", "num", "eps")
_BLOCK = ("stride", "num", "eps")
_stem_fwd = jax.jit(_stem, static_argnames=_STEM)
_block_fwd = jax.jit(_block, static_argnames=_BLOCK)


@partial(jax.jit, static_argnames=_STEM)
def _stem_bwd(images, w, bn, key, g, **static):
    _, vjp = jax.vjp(lambda a, b: _stem(images, a, b, key, **static), w, bn)
    return vjp(g)


@partial(jax.jit, static_argnames=_BLOCK)
def _block_bwd(x, p, key, g, **static):
    _, vjp = jax.vjp(lambda a, q: _block(a, q, key, **static), x, p)
    return vjp(g)


_head_grad = jax.jit(jax.value_and_grad(_head, argnums=(0, 1)),
                     static_argnames=("num",))


@jax.jit
def _sgd(grads, mu, params, lr, momentum, weight_decay):
    mu = jax.tree.map(lambda g, m, p: momentum * m + g + weight_decay * p,
                      grads, mu, params)
    return jax.tree.map(lambda p, m: p - lr * m, params, mu), mu


def step(params, mu, batch, key, cfg: dict, num: Numerics):
    """One SGD-momentum step: ``(params, mu, loss, grads)`` after it."""
    eps = cfg["bn_eps"]
    strides = [2 if (b == 0 and s > 0) else 1
               for s, depth in enumerate(cfg["depths"]) for b in range(depth)]
    keys = [jax.random.fold_in(key, i) for i in range(len(strides) + 1)]
    stem = dict(stride=cfg["stem_stride"], pool=cfg["stem_pool"], num=num,
                eps=eps)
    acts = [_stem_fwd(batch["image"], params["stem"]["w"], params["bn_stem"],
                      keys[0], **stem)]
    for blk, k, s in zip(params["blocks"], keys[1:], strides):
        acts.append(_block_fwd(acts[-1], blk, k, stride=s, num=num, eps=eps))
    loss, (g, g_fc) = _head_grad(acts[-1], params["fc"], batch["label"],
                                 num=num)
    g_blocks = [None] * len(strides)
    for b in reversed(range(len(strides))):
        g, g_blocks[b] = _block_bwd(acts[b], params["blocks"][b], keys[b + 1],
                                    g, stride=strides[b], num=num, eps=eps)
    g_w, g_bn = _stem_bwd(batch["image"], params["stem"]["w"],
                          params["bn_stem"], keys[0], g, **stem)
    grads = {"blocks": g_blocks, "bn_stem": g_bn, "fc": g_fc,
             "stem": {"w": g_w}}
    params, mu = _sgd(grads, mu, params, cfg["lr"], cfg["momentum"],
                      cfg["weight_decay"])
    return params, mu, loss, grads
