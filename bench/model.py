"""Shapes and seeded weights of the benchmark's CNN configurations.

A configuration file (``bench/configs/<name>.json``) gives a ResNet's
stages (``depths``, ``widths``), its stem and its input.  This module turns
that into the list of convolutions (used by ``work.py`` and the reference)
and into the parameter tree the program's train step takes, with weights
drawn here from the seed: conv weights He-normal, batch-norm scale 1 and
shift 0, the classifier Glorot-uniform with a zero bias.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Conv:
    """One convolution of the network, per image (NCHW, square)."""

    path: tuple  # where its weight sits in the parameter tree
    c_in: int
    c_out: int
    k: int
    stride: int
    h_in: int
    quantized: bool  # the paper keeps the stem (first conv) in float

    @property
    def h_out(self) -> int:
        return -(-self.h_in // self.stride)  # "SAME" padding

    @property
    def macs(self) -> int:
        """Multiply-accumulates of the forward conv for one image."""
        return self.h_out * self.h_out * self.c_out * self.c_in * self.k * self.k


def widths(cfg: dict) -> list[int]:
    """Stage widths after ``width_mult`` (at least 4 channels)."""
    return [max(4, int(round(w * cfg["width_mult"]))) for w in cfg["widths"]]


def convs(cfg: dict) -> list[Conv]:
    """Every convolution, in the order the forward pass runs them."""
    ws = widths(cfg)
    h = cfg["in_hw"]
    out = [Conv(("stem", "w"), cfg["in_ch"], ws[0], cfg["stem_kernel"],
                cfg["stem_stride"], h, False)]
    h = out[0].h_out
    if cfg["stem_pool"]:
        h = -(-h // 2)  # 3x3 max pool, stride 2, "SAME"
    c_in, bi = ws[0], 0
    for si, (depth, w) in enumerate(zip(cfg["depths"], ws)):
        for b in range(depth):
            stride = 2 if (b == 0 and si > 0) else 1
            blk = ("blocks", bi)
            out.append(Conv(blk + ("conv1", "w"), c_in, w, 3, stride, h, True))
            h_out = -(-h // stride)
            out.append(Conv(blk + ("conv2", "w"), w, w, 3, 1, h_out, True))
            if stride != 1 or c_in != w:
                out.append(Conv(blk + ("proj", "w"), c_in, w, 1, stride, h,
                                True))
            h, c_in, bi = h_out, w, bi + 1
    return out


def fc_shape(cfg: dict) -> tuple[int, int]:
    return widths(cfg)[-1], cfg["num_classes"]


def init_params(key: jax.Array, cfg: dict) -> dict:
    """The parameter tree, drawn from ``key`` (call it under ``jax.jit``)."""
    cs = convs(cfg)
    keys = jax.random.split(key, len(cs) + 1)
    ws = widths(cfg)

    def conv_w(k, c: Conv):
        fan_in = c.c_in * c.k * c.k
        shape = (c.c_out, c.c_in, c.k, c.k)
        return {"w": jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(2.0 / fan_in)}

    def bn(c):
        return {"gamma": jnp.ones((c,), jnp.float32),
                "beta": jnp.zeros((c,), jnp.float32)}

    p = {"stem": conv_w(keys[0], cs[0]), "bn_stem": bn(ws[0]), "blocks": []}
    for c, k in zip(cs[1:], keys[1:-1]):
        _, bi, name, _ = c.path
        if bi == len(p["blocks"]):
            p["blocks"].append({})
        blk = p["blocks"][bi]
        blk[name] = conv_w(k, c)
        blk["bn_proj" if name == "proj" else "bn" + name[-1]] = bn(c.c_out)
    d_in, d_out = fc_shape(cfg)
    lim = jnp.sqrt(6.0 / (d_in + d_out))
    p["fc"] = {"w": jax.random.uniform(keys[-1], (d_in, d_out), jnp.float32,
                                       -lim, lim),
               "b": jnp.zeros((d_out,), jnp.float32)}
    return p


def leaf_paths(params: dict) -> list[str]:
    """Readable names of the tree's leaves, in ``jax.tree.leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [jax.tree_util.keystr(path) for path, _ in flat]
