"""The yardstick: logical operations and bytes of one training step.

Counted from the configuration's convolution shapes (the shape arithmetic
of the program's ``models.cnn.count_ops``, kept here), never from what an
implementation does: no padding, no im2col expansion, no rounding bits.

Per convolution and image, ``macs`` is the forward multiply-accumulates.
A training step runs three GEMMs of that size on each convolution (forward,
weight gradient, input gradient), except that the stem needs no input
gradient; the classifier's three matrix products count the same way.  An
operation is a multiply or an add, so a GEMM of ``m*k*n`` MACs is
``2*m*k*n`` operations.
"""
from __future__ import annotations

from bench.model import Conv, convs, fc_shape


def forward_macs(cfg: dict) -> int:
    """Forward multiply-accumulates of one image (convs and classifier)."""
    d_in, d_out = fc_shape(cfg)
    return sum(c.macs for c in convs(cfg)) + d_in * d_out


def train_ops_per_image(cfg: dict) -> int:
    """Operations of one image's forward and backward pass."""
    d_in, d_out = fc_shape(cfg)
    macs = 3 * d_in * d_out
    for i, c in enumerate(convs(cfg)):
        macs += (2 if i == 0 else 3) * c.macs
    return 2 * macs


def _sizes(c: Conv, batch: int) -> tuple[int, int, int]:
    """Elements of the conv's input x, weight w and output error e."""
    x = batch * c.c_in * c.h_in * c.h_in
    w = c.c_out * c.c_in * c.k * c.k
    e = batch * c.c_out * c.h_out * c.h_out
    return x, w, e


def quantize_bytes(cfg: dict, batch: int, code_bits: int) -> float:
    """Least HBM bytes the quantizers of one step must move: x, w and e of
    every quantized conv read once in float32, and one set of codes
    written for each operand of each of its three GEMMs (x and w for the
    forward, x and e for the weight gradient, e and w for the input
    gradient)."""
    total = 0.0
    for c in convs(cfg):
        if c.quantized:
            x, w, e = _sizes(c, batch)
            total += 4 * (x + w + e) + 2 * (x + w + e) * code_bits / 8
    return total


def gemm_work(cfg: dict, batch: int, code_bits: int) -> tuple[float, float]:
    """(operations, least HBM bytes) of the quantized GEMMs of one step:
    ``2*M*K*N`` for each of the three GEMMs of every quantized conv, and
    their operand codes read at ``code_bits`` plus their float32 results
    (y, dw, dx) written."""
    ops = 0.0
    nbytes = 0.0
    for c in convs(cfg):
        if c.quantized:
            x, w, e = _sizes(c, batch)
            ops += 3 * 2 * batch * c.macs
            nbytes += 2 * (x + w + e) * code_bits / 8 + 4 * (e + w + x)
    return ops, nbytes
