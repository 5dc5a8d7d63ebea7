"""End-to-end driver (the paper's own experiment at reduced scale): train
ResNet-20 on (synthetic) CIFAR with the MLS low-bit training framework and
compare against the fp32 baseline — plus checkpoint/restart fault-tolerance
and straggler monitoring along the way.

Run:  PYTHONPATH=src python examples/train_cifar_lowbit.py --steps 200
(defaults are CPU-friendly; --width 1.0 --hw 32 --steps 1000 approaches the
real ResNet-20 setup.)
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.core import FMT_CIFAR, FMT_IMAGENET, QuantConfig
from repro.data import make_cifar_iterator
from repro.models.cnn import CNNConfig, init_cnn
from repro.optim import sgdm_init, step_decay_schedule
from repro.train import (
    CheckpointManager,
    StragglerMonitor,
    enable_compile_cache,
    make_cnn_train_step,
)


def train(variant, qcfg, args, ckpt_dir=None):
    cfg = CNNConfig(arch="resnet20", num_classes=10,
                    width_mult=args.width, in_hw=args.hw)
    params = init_cnn(jax.random.key(0), cfg)
    opt = sgdm_init(params)
    nxt, ds = make_cifar_iterator(batch=args.batch, hw=args.hw)
    lr_fn = step_decay_schedule(0.05, [args.steps // 2, 3 * args.steps // 4])
    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    mon = StragglerMonitor()

    step = make_cnn_train_step(cfg, qcfg, lr_fn, rounding_seed=7)

    accs = []
    for i in range(args.steps):
        batch, ds = nxt(ds)
        mon.start()
        params, opt, l, a = step(params, opt, batch, jnp.int32(i))
        accs.append(float(a))  # waits for the step: the monitor times it
        dt = mon.stop()
        if mgr and (i + 1) % 50 == 0:
            mgr.save(i + 1, {"params": params, "opt": opt, "data": ds},
                     blocking=False)
        if (i + 1) % max(args.steps // 10, 1) == 0:
            k = max(len(accs) // 5, 1)
            print(f"  [{variant}] step {i+1}: loss={float(l):.3f} "
                  f"acc(avg)={sum(accs[-k:])/k:.3f} ({dt:.2f}s/step)")
    if mgr:
        mgr.wait()
        print(f"  [{variant}] checkpoints: latest step {mgr.latest_step()}, "
              f"straggler report {mon.report()['straggler_steps']}")
    k = max(len(accs) // 5, 1)
    return sum(accs[-k:]) / k


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--hw", type=int, default=16)
    ap.add_argument("--width", type=float, default=0.5)
    ap.add_argument("--backend", choices=["fake_quant", "pallas"],
                    default="fake_quant",
                    help="arithmetic for the quantized convs/GEMMs: fake-quant "
                         "simulation or the quantized-domain Pallas kernels "
                         "(Mosaic on a TPU; interpret mode elsewhere: slow, "
                         "use tiny --steps)")
    args = ap.parse_args()
    enable_compile_cache()

    # the Pallas backend groups along im2col k-blocks; small blocks keep the
    # reduced CPU shapes from being all padding
    qkw = dict(backend=args.backend)
    if args.backend == "pallas":
        qkw["k_block"] = 32
    variants = [
        ("fp32", None),
        ("mls<2,4>", QuantConfig(fmt=FMT_IMAGENET, **qkw)),
        ("mls<2,1>", QuantConfig(fmt=FMT_CIFAR, **qkw)),
    ]
    results = {}
    with tempfile.TemporaryDirectory() as td:
        for name, qcfg in variants:
            print(f"== training {name} ==")
            results[name] = train(name, qcfg, args,
                                  ckpt_dir=f"{td}/{name}" if name != "fp32" else None)
    print("\n== final accuracy (paper Table II analogue) ==")
    base = results["fp32"]
    for name, acc in results.items():
        print(f"  {name:10s} acc={acc:.3f} drop={base - acc:+.3f}")


if __name__ == "__main__":
    main()
