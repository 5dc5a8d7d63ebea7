"""Chip smoke run: the paper's low-bit ResNet-20 CIFAR training on one TPU.

Trains ResNet-20 at the paper's CIFAR setting (width 1, 32x32 inputs,
batch 128, synthetic data from ``--seed``) with every quantized conv on the
quantized-domain Pallas kernels compiled by Mosaic, through the same entry
points the trainers use (``init_cnn``, ``make_cifar_iterator``,
``make_cnn_train_step``).  Phases, all in this one process:

1. device check — a TPU, and the Pallas kernels compiled (not interpreted);
2. compile the jitted train step ahead of time and count its Mosaic kernels;
3. train ``STEPS`` steps on the im2col lowering (default ``k_block``);
4. the same steps with a ``k_block`` that makes the implicit-GEMM conv
   legal for every 3x3 conv of the network;
5. parity: one quantized conv layer (output and both gradients) against the
   pure-jnp oracle of ``kernels/ref.py`` on the same chip, bit for bit.

Any failure exits non-zero.  The last line of standard output is a JSON
object naming the device, printed only when every phase passed.

Run from the repository root::

    python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere

BATCH = 128
HW = 32
STEPS = 5
LR = 0.05  # examples/train_cifar_lowbit.py's base rate
# k_block = cb * 3 * 3 with cb = 16 | C for C in {16, 32, 64}: the implicit
# conv's groups are whole channels' taps (accumulation: 14 + 8 = 22 bits).
IMPLICIT_K_BLOCK = 144


class SmokeFailure(Exception):
    pass


def _say(msg: str) -> None:
    print(msg, flush=True)


def check_device():
    import jax

    from repro.kernels import INTERPRET_ENV_VAR, default_interpret

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX sees {len(jax.devices())} {dev.platform} "
            f"device(s) ({dev.device_kind})")
    if default_interpret():
        raise SmokeFailure(
            f"Pallas kernels would run in interpret mode "
            f"({INTERPRET_ENV_VAR}={os.environ.get(INTERPRET_ENV_VAR)!r}); "
            f"the smoke run needs them compiled")
    _say(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    return dev


def compile_step(cfg, qcfg, params, opt, batch, seed: int, tag: str):
    import jax
    import jax.numpy as jnp

    from repro.train import make_cnn_train_step

    step = jax.jit(make_cnn_train_step(cfg, qcfg, LR, rounding_seed=seed))
    t0 = time.perf_counter()
    lowered = step.lower(params, opt, batch, jnp.int32(0))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    n_kernels = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    _say(f"[compile:{tag}] lower {t1 - t0:.2f}s compile {t2 - t1:.2f}s "
         f"tpu_custom_call={n_kernels}")
    if n_kernels == 0:
        raise SmokeFailure(
            f"{tag}: the compiled step holds no tpu_custom_call — the "
            f"quantized convs did not lower to Mosaic kernels")
    return compiled


def train(compiled, params, opt, nxt, ds, tag: str):
    import jax
    import jax.numpy as jnp

    # warm-up: the first call transfers and initializes; not reported
    batch, ds = nxt(ds)
    params, opt, loss, _ = compiled(params, opt, batch, jnp.int32(0))
    jax.block_until_ready(loss)
    losses = []
    for i in range(1, STEPS + 1):
        batch, ds = nxt(ds)
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        params, opt, loss, acc = compiled(params, opt, batch, jnp.int32(i))
        jax.block_until_ready((params, opt, loss, acc))
        dt = time.perf_counter() - t0
        loss = float(loss)
        losses.append(loss)
        _say(f"[train:{tag}] step {i} loss {loss!r} acc {float(acc)!r} "
             f"time {dt * 1e3:.3f} ms")
        if not math.isfinite(loss):
            raise SmokeFailure(f"{tag}: non-finite loss {loss} at step {i}")
    return losses


def run_training(cfg, qcfg, seed: int, tag: str):
    import jax

    from repro.data import make_cifar_iterator
    from repro.models.cnn import init_cnn
    from repro.optim import sgdm_init

    params = init_cnn(jax.random.key(seed), cfg)
    opt = sgdm_init(params)
    nxt, ds = make_cifar_iterator(batch=BATCH, hw=HW, seed=seed)
    batch, _ = nxt(ds)
    compiled = compile_step(cfg, qcfg, params, opt, batch, seed, tag)
    return train(compiled, params, opt, nxt, ds, tag)


def check_implicit_selected(qcfg):
    from repro.kernels import conv_geometry, resolve_conv_impl

    for c, hw in ((16, 32), (32, 16), (64, 8)):
        geom = conv_geometry((BATCH, c, hw, hw), (c, c, 3, 3), (1, 1), "SAME")
        impl = resolve_conv_impl(geom, qcfg)
        if impl != "implicit":
            raise SmokeFailure(
                f"k_block={qcfg.k_block} resolves the {c}-channel 3x3 conv "
                f"to {impl!r}, not the implicit kernel")


def parity(qcfg, seed: int, tag: str):
    """One quantized conv layer at ResNet-20's first-stage shape: Mosaic
    kernels against the jnp oracle, forward and both gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import conv_fused_grads_ref, lowbit_conv_fused
    from repro.kernels.lowbit_conv import lowbit_conv_fused_ref

    kx, kw, kg, kq = jax.random.split(jax.random.key(seed + 1), 4)
    x = jax.random.normal(kx, (BATCH, 16, HW, HW), jnp.float32)
    w = jax.random.normal(kw, (16, 16, 3, 3), jnp.float32) * 0.1
    g = jax.random.normal(kg, (BATCH, 16, HW, HW), jnp.float32)
    conv = (1, 1), "SAME"

    @jax.jit
    def kernel(x, w, g, key):
        y, vjp = jax.vjp(
            lambda a, b: lowbit_conv_fused(a, b, key, *conv, qcfg), x, w)
        return (y, *vjp(g))

    @jax.jit
    def oracle(x, w, g, key):
        y = lowbit_conv_fused_ref(x, w, key, *conv, qcfg)
        return (y, *conv_fused_grads_ref(x, w, g, key, *conv, qcfg))

    got = jax.block_until_ready(kernel(x, w, g, kq))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(oracle(x, w, g, kq))
    diffs = {}
    for name, a, b in zip(("y", "dx", "dw"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        diffs[name] = float(np.max(np.abs(a - b)))
        mismatched = int(np.count_nonzero(a != b))
        _say(f"[parity:{tag}] {name} {a.shape} max|kernel - ref| "
             f"{diffs[name]!r} mismatched {mismatched}/{a.size}")
    if any(d != 0.0 for d in diffs.values()):
        raise SmokeFailure(
            f"{tag}: Mosaic kernels are not bit-identical to kernels/ref.py "
            f"({diffs})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.core import FMT_IMAGENET, QuantConfig
        from repro.models.cnn import CNNConfig
        from repro.train import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run from the "
              f"repository root", file=sys.stderr)
        return 2

    try:
        dev = check_device()
        enable_compile_cache()
        cfg = CNNConfig(arch="resnet20", num_classes=10, width_mult=1.0,
                        in_hw=HW)
        im2col = QuantConfig(fmt=FMT_IMAGENET, backend="pallas")
        implicit = QuantConfig(fmt=FMT_IMAGENET, backend="pallas",
                               k_block=IMPLICIT_K_BLOCK)
        check_implicit_selected(implicit)
        _say(f"[config] resnet20 width 1.0 {HW}x{HW} batch {BATCH} "
             f"fmt {im2col.fmt} grouping {im2col.grouping} "
             f"k_block {im2col.k_block} (im2col) / {implicit.k_block} "
             f"(implicit) seed {args.seed}")
        run_training(cfg, im2col, args.seed, "im2col")
        run_training(cfg, implicit, args.seed, "implicit")
        parity(im2col, args.seed, "im2col")
        parity(implicit, args.seed, "implicit")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
