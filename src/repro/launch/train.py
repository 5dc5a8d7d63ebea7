"""Production training launcher.

Binds the mesh + logical sharding rules, builds the (optionally
microbatched) train step, places sharded parameters, and runs the train
loop with async checkpointing, restart-on-resume and straggler monitoring.

On real hardware::

    python -m repro.launch.train --arch qwen2-72b --shape train_4k \
        --multi-pod --steps 1000 --ckpt-dir /ckpts/qwen

On this CPU container use ``--smoke`` (reduced config, 1-device mesh) —
the code path (sharding, checkpointing, loop) is identical.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import (
    SHAPES, RunConfig, get_config, get_smoke_config, shape_model_config,
)
from repro.data import make_lm_iterator
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.specs import choose_microbatch
from repro.models import lm
from repro.parallel import DEFAULT_RULES, axis_rules
from repro.parallel.specs import batch_shardings, param_shardings
from repro.train import (
    CheckpointManager,
    StragglerMonitor,
    enable_compile_cache,
    make_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on a local mesh (CPU)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0, help="override batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq")
    args = ap.parse_args()
    enable_compile_cache()

    shape = SHAPES[args.shape]
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        mesh = make_local_mesh(1, 1)
        batch_size = args.batch or 8
        seq = args.seq or 64
    else:
        cfg = shape_model_config(get_config(args.arch), shape)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        batch_size = args.batch or shape.global_batch
        seq = args.seq or shape.seq_len

    rules = DEFAULT_RULES
    mb = choose_microbatch(cfg, shape, mesh) if not args.smoke else 0
    run = RunConfig(model=cfg, shape=shape, microbatch=mb)
    train_step, opt_init = make_train_step(run)

    with mesh, axis_rules(rules, mesh):
        params = lm.init_lm(jax.random.key(run.seed), cfg)
        p_shard = param_shardings(params, mesh, rules)
        params = jax.tree.map(jax.device_put, params, p_shard)
        opt = opt_init(params)
        step_fn = jax.jit(train_step, donate_argnums=(0, 1))

        nxt, ds = make_lm_iterator(batch=batch_size, seq=seq, vocab=cfg.vocab)
        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if mgr and mgr.latest_step() is not None:
            st = mgr.restore({"params": params, "opt": opt, "data": ds},
                             shardings=None)
            params, opt, ds = st["params"], st["opt"], st["data"]
            start = mgr.latest_step()
            print(f"resumed from step {start}")

        mon = StragglerMonitor()
        for i in range(start, args.steps):
            batch, ds = nxt(ds)
            batch = jax.tree.map(
                lambda x: jax.device_put(x, batch_shardings(
                    {"tokens": x}, mesh, rules)["tokens"])
                if x.ndim == 2 else x, batch)
            mon.start()
            params, opt, metrics = step_fn(params, opt, batch)
            jax.block_until_ready(metrics["loss"])
            dt = mon.stop()
            if (i + 1) % 10 == 0 or i == start:
                print(f"step {i+1}: loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.2f} {dt:.2f}s")
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, {"params": params, "opt": opt, "data": ds},
                         blocking=False)
        if mgr:
            mgr.wait()
        print("straggler report:", mon.report())


if __name__ == "__main__":
    main()
