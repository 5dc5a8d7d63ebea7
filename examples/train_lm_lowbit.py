"""Train a small LM (reduced glm4-9b family) with MLS low-bit matmuls through
the full production stack: RunConfig -> make_train_step (grad accumulation,
clipping, schedules) -> checkpoint/restart.

Run:  PYTHONPATH=src python examples/train_lm_lowbit.py --steps 60
Scale up (real hardware): --layers 12 --d-model 768 gives a ~100M model.
"""
import argparse
import dataclasses
import tempfile

import jax

from repro.configs import get_smoke_config
from repro.configs.base import RunConfig, SHAPES
from repro.data import make_lm_iterator
from repro.models import lm
from repro.train import (
    CheckpointManager,
    StragglerMonitor,
    enable_compile_cache,
    make_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--no-quant", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config("glm4-9b")
    cfg = dataclasses.replace(
        cfg, n_layers=args.layers, d_model=args.d_model,
        d_ff=args.d_model * 3 // 2, vocab=1024, quant=not args.no_quant,
    )
    n = cfg.n_params()
    print(f"model: {cfg.name} reduced, {n/1e6:.1f}M params, quant={cfg.quant}")

    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    microbatch=args.microbatch, optimizer="adamw", lr=3e-3)
    train_step, opt_init = make_train_step(run)
    step = jax.jit(train_step, donate_argnums=(0, 1))

    params = lm.init_lm(jax.random.key(0), cfg)
    opt = opt_init(params)
    nxt, ds = make_lm_iterator(batch=args.batch, seq=args.seq, vocab=cfg.vocab)
    mon = StragglerMonitor()

    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep=2)
        for i in range(args.steps):
            batch, ds = nxt(ds)
            mon.start()
            params, opt, m = step(params, opt, batch)
            dt = mon.stop()
            if (i + 1) % max(args.steps // 10, 1) == 0:
                print(f"  step {i+1}: loss={float(m['loss']):.3f} "
                      f"gnorm={float(m['grad_norm']):.2f} "
                      f"lr={float(m['lr']):.2e} ({dt:.2f}s)")
            if (i + 1) % 25 == 0:
                mgr.save(i + 1, {"params": params, "opt": opt, "data": ds},
                         blocking=False)
        mgr.wait()

        # fault-tolerance demo: restore and take one more step
        if mgr.latest_step():
            r = mgr.restore({"params": params, "opt": opt, "data": ds})
            b, _ = nxt(r["data"])
            _, _, m = step(r["params"], r["opt"], b)
            print(f"restored from step {mgr.latest_step()}, next-step "
                  f"loss={float(m['loss']):.3f} (restart-reproducible)")
    print(f"straggler steps flagged: {mon.report()['straggler_steps']}")


if __name__ == "__main__":
    main()
