"""The paper's CNN model zoo: ResNet-20/18/34, VGG-16, GoogleNet.

Every quantizable conv/FC takes the layer's :class:`QuantConfig`; per the
paper (Sec. VI-A) the **first conv and the final classifier stay
unquantized**.  BN runs in fp32.  A ``width_mult``/``depth`` knob produces
the reduced smoke/training configs used on CPU.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import jax
import jax.numpy as jnp

from repro.core import QuantConfig
from . import nn

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    arch: str  # resnet20 | resnet18 | resnet34 | vgg16 | googlenet
    num_classes: int = 10
    width_mult: float = 1.0
    in_hw: int = 32  # 32 for CIFAR, 224 for ImageNet variants
    in_ch: int = 3

    def scaled(self, c: int) -> int:
        return max(4, int(round(c * self.width_mult)))


def _key_iter(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


def _fold(key, tag: int):
    return None if key is None else jax.random.fold_in(key, tag)


# Activations and pooling run under the "act" scope, so a profile of the
# compiled step can tell them from the conv, bn and fc layers.
def _relu(x):
    with jax.named_scope("act"):
        return jax.nn.relu(x)


def _max_pool(x, k: int, s: int, padding: str):
    with jax.named_scope("act"):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, k, k), (1, 1, s, s), padding)


def _mean_pool(x):
    with jax.named_scope("act"):
        return jnp.mean(x, axis=(2, 3))


# ---------------------------------------------------------------------------
# ResNet (CIFAR basic-block and ImageNet basic-block variants)
# ---------------------------------------------------------------------------
def _init_block(ks, c_in, c_out, stride):
    p = {
        "conv1": nn.init_conv(next(ks), c_in, c_out, 3),
        "bn1": nn.init_batchnorm(c_out),
        "conv2": nn.init_conv(next(ks), c_out, c_out, 3),
        "bn2": nn.init_batchnorm(c_out),
    }
    if stride != 1 or c_in != c_out:
        p["proj"] = nn.init_conv(next(ks), c_in, c_out, 1)
        p["bn_proj"] = nn.init_batchnorm(c_out)
    return p


def _block(p, x, stride, qcfg, key, tag):
    h = nn.conv2d(p["conv1"], x, stride, "SAME", qcfg, _fold(key, tag))
    h = _relu(nn.batchnorm(p["bn1"], h))
    h = nn.conv2d(p["conv2"], h, 1, "SAME", qcfg, _fold(key, tag + 1))
    h = nn.batchnorm(p["bn2"], h)
    if "proj" in p:
        x = nn.batchnorm(
            p["bn_proj"],
            nn.conv2d(p["proj"], x, stride, "SAME", qcfg, _fold(key, tag + 2)),
        )
    return _relu(nn.ew_add(h, x))


_RESNET_STAGES = {
    "resnet20": ([3, 3, 3], [16, 32, 64], False),
    "resnet18": ([2, 2, 2, 2], [64, 128, 256, 512], True),
    "resnet34": ([3, 4, 6, 3], [64, 128, 256, 512], True),
}


def init_resnet(key, cfg: CNNConfig):
    ks = _key_iter(key)
    depths, widths, imagenet_stem = _RESNET_STAGES[cfg.arch]
    widths = [cfg.scaled(w) for w in widths]
    p = {}
    if imagenet_stem:
        p["stem"] = nn.init_conv(next(ks), cfg.in_ch, widths[0], 7)
    else:
        p["stem"] = nn.init_conv(next(ks), cfg.in_ch, widths[0], 3)
    p["bn_stem"] = nn.init_batchnorm(widths[0])
    c_in = widths[0]
    blocks = []
    for si, (d, w) in enumerate(zip(depths, widths)):
        for bi in range(d):
            stride = 2 if (bi == 0 and si > 0) else 1
            blocks.append(_init_block(ks, c_in, w, stride))
            c_in = w
    p["blocks"] = blocks
    p["fc"] = nn.init_linear(next(ks), c_in, cfg.num_classes, bias=True)
    return p


def apply_resnet(p, x, cfg: CNNConfig, qcfg: QuantConfig | None, key=None):
    depths, widths, imagenet_stem = _RESNET_STAGES[cfg.arch]
    # first layer unquantized (paper Sec. VI-A)
    h = nn.conv2d(p["stem"], x, 2 if imagenet_stem else 1, "SAME", None)
    h = _relu(nn.batchnorm(p["bn_stem"], h))
    if imagenet_stem:
        h = _max_pool(h, 3, 2, "SAME")
    bi_flat, tag = 0, 0
    for si, d in enumerate(depths):
        for bi in range(d):
            stride = 2 if (bi == 0 and si > 0) else 1
            h = _block(p["blocks"][bi_flat], h, stride, qcfg, key, tag)
            bi_flat += 1
            tag += 3
    h = _mean_pool(h)
    return nn.linear(p["fc"], h, None)  # last layer unquantized


# ---------------------------------------------------------------------------
# VGG-16
# ---------------------------------------------------------------------------
_VGG16 = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
          512, 512, 512, "M", 512, 512, 512, "M"]


def init_vgg16(key, cfg: CNNConfig):
    ks = _key_iter(key)
    p, c_in, convs = {}, cfg.in_ch, []
    for v in _VGG16:
        if v == "M":
            continue
        c = cfg.scaled(v)
        convs.append({"conv": nn.init_conv(next(ks), c_in, c, 3),
                      "bn": nn.init_batchnorm(c)})
        c_in = c
    p["convs"] = convs
    p["fc"] = nn.init_linear(next(ks), c_in, cfg.num_classes, bias=True)
    return p


def apply_vgg16(p, x, cfg: CNNConfig, qcfg: QuantConfig | None, key=None):
    h, ci, tag = x, 0, 0
    for v in _VGG16:
        if v == "M":
            h = _max_pool(h, 2, 2, "VALID")
            continue
        q = None if ci == 0 else qcfg  # first conv unquantized
        blk = p["convs"][ci]
        h = _relu(nn.batchnorm(blk["bn"], nn.conv2d(
            blk["conv"], h, 1, "SAME", q, _fold(key, tag))))
        ci += 1
        tag += 1
    h = _mean_pool(h)
    return nn.linear(p["fc"], h, None)


# ---------------------------------------------------------------------------
# GoogleNet (Inception v1, BN variant, no aux heads)
# ---------------------------------------------------------------------------
# (1x1, (3x3red, 3x3), (5x5red, 5x5), pool_proj)
_INCEPTION = [
    ("3a", 64, (96, 128), (16, 32), 32),
    ("3b", 128, (128, 192), (32, 96), 64),
    ("M", 0, (0, 0), (0, 0), 0),
    ("4a", 192, (96, 208), (16, 48), 64),
    ("4b", 160, (112, 224), (24, 64), 64),
    ("4c", 128, (128, 256), (24, 64), 64),
    ("4d", 112, (144, 288), (32, 64), 64),
    ("4e", 256, (160, 320), (32, 128), 128),
    ("M", 0, (0, 0), (0, 0), 0),
    ("5a", 256, (160, 320), (32, 128), 128),
    ("5b", 384, (192, 384), (48, 128), 128),
]


def _init_inception(ks, c_in, cfg: CNNConfig, spec):
    _, c1, (c3r, c3), (c5r, c5), cp = spec
    s = cfg.scaled
    return {
        "b1": {"conv": nn.init_conv(next(ks), c_in, s(c1), 1), "bn": nn.init_batchnorm(s(c1))},
        "b3r": {"conv": nn.init_conv(next(ks), c_in, s(c3r), 1), "bn": nn.init_batchnorm(s(c3r))},
        "b3": {"conv": nn.init_conv(next(ks), s(c3r), s(c3), 3), "bn": nn.init_batchnorm(s(c3))},
        "b5r": {"conv": nn.init_conv(next(ks), c_in, s(c5r), 1), "bn": nn.init_batchnorm(s(c5r))},
        "b5": {"conv": nn.init_conv(next(ks), s(c5r), s(c5), 5), "bn": nn.init_batchnorm(s(c5))},
        "bp": {"conv": nn.init_conv(next(ks), c_in, s(cp), 1), "bn": nn.init_batchnorm(s(cp))},
    }


def _cbr(blk, x, k, stride, qcfg, key, tag):
    return _relu(nn.batchnorm(blk["bn"], nn.conv2d(
        blk["conv"], x, stride, "SAME", qcfg, _fold(key, tag))))


def _inception(p, x, qcfg, key, tag):
    b1 = _cbr(p["b1"], x, 1, 1, qcfg, key, tag)
    b3 = _cbr(p["b3"], _cbr(p["b3r"], x, 1, 1, qcfg, key, tag + 1), 3, 1, qcfg, key, tag + 2)
    b5 = _cbr(p["b5"], _cbr(p["b5r"], x, 1, 1, qcfg, key, tag + 3), 5, 1, qcfg, key, tag + 4)
    pool = _max_pool(x, 3, 1, "SAME")
    bp = _cbr(p["bp"], pool, 1, 1, qcfg, key, tag + 5)
    return jnp.concatenate([b1, b3, b5, bp], axis=1)


def init_googlenet(key, cfg: CNNConfig):
    ks = _key_iter(key)
    s = cfg.scaled
    p = {
        "stem1": {"conv": nn.init_conv(next(ks), cfg.in_ch, s(64), 7), "bn": nn.init_batchnorm(s(64))},
        "stem2": {"conv": nn.init_conv(next(ks), s(64), s(64), 1), "bn": nn.init_batchnorm(s(64))},
        "stem3": {"conv": nn.init_conv(next(ks), s(64), s(192), 3), "bn": nn.init_batchnorm(s(192))},
    }
    c_in, mods = s(192), []
    for spec in _INCEPTION:
        if spec[0] == "M":
            mods.append(None)
            continue
        mods.append(_init_inception(ks, c_in, cfg, spec))
        _, c1, (_, c3), (_, c5), cp = spec
        c_in = s(c1) + s(c3) + s(c5) + s(cp)
    p["inception"] = [m for m in mods if m is not None]
    p["fc"] = nn.init_linear(next(ks), c_in, cfg.num_classes, bias=True)
    return p


def apply_googlenet(p, x, cfg: CNNConfig, qcfg: QuantConfig | None, key=None):
    imagenet = cfg.in_hw >= 128
    h = _cbr(p["stem1"], x, 7, 2 if imagenet else 1, None, None, 0)  # unquantized
    if imagenet:
        h = _max_pool(h, 3, 2, "SAME")
    h = _cbr(p["stem2"], h, 1, 1, qcfg, key, 1)
    h = _cbr(p["stem3"], h, 3, 1, qcfg, key, 2)
    if imagenet:
        h = _max_pool(h, 3, 2, "SAME")
    mi, tag = 0, 10
    for spec in _INCEPTION:
        if spec[0] == "M":
            h = _max_pool(h, 3, 2, "SAME")
            continue
        h = _inception(p["inception"][mi], h, qcfg, key, tag)
        mi += 1
        tag += 6
    h = _mean_pool(h)
    return nn.linear(p["fc"], h, None)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def init_cnn(key, cfg: CNNConfig):
    if cfg.arch.startswith("resnet"):
        return init_resnet(key, cfg)
    if cfg.arch == "vgg16":
        return init_vgg16(key, cfg)
    if cfg.arch == "googlenet":
        return init_googlenet(key, cfg)
    raise ValueError(cfg.arch)


def apply_cnn(p, x, cfg: CNNConfig, qcfg: QuantConfig | None = None, key=None):
    if cfg.arch.startswith("resnet"):
        return apply_resnet(p, x, cfg, qcfg, key)
    if cfg.arch == "vgg16":
        return apply_vgg16(p, x, cfg, qcfg, key)
    if cfg.arch == "googlenet":
        return apply_googlenet(p, x, cfg, qcfg, key)
    raise ValueError(cfg.arch)


def count_ops(cfg: CNNConfig, batch: int = 1):
    """Exact op counts via shape tracing (paper Table I methodology)."""
    with nn.OpTrace() as tr:
        def run(x):
            p = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.key(0))
            p = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p)
            return apply_cnn(p, x, cfg)
        jax.eval_shape(run, jax.ShapeDtypeStruct((batch, cfg.in_ch, cfg.in_hw, cfg.in_hw), jnp.float32))
    return tr.ops
