"""Readings that the limits of ``correct`` are set from, in one process.

For each seed this reads the numbers of ``check.py`` for

* ``program``: the program's jitted train step, driven through its
  set-up steps exactly as ``run.py`` does, against the reference;
* ``control``: the reference computed in the nearest precision below the
  one the cell states (the workload's ``control``), with rounding noise
  of its own, in the program's place;
* ``half_batch``: the reference, at the cell's precision and with noise
  of its own, on the first half of each batch (the mean over the rest);
* ``unchanged``: a step that returns its state unchanged (the reference
  at a learning rate of 0 gives its losses; momentum and change are 0).

The program's step is jitted once for all seeds.  On the chip::

    python bench/control.py --workload <cell> --seeds 1-12 --control-seeds 1-3

Each seed's readings are one JSON line on standard output, with the
losses and per-leaf norms they were computed from under ``norms``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from bench import check, run  # noqa: E402


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def _as_program(ref: dict, p0_host, weight_decay: float) -> dict:
    """A reference run in the program's place (momentum after one step is
    the gradient plus weight decay)."""
    import jax

    p0 = jax.tree.leaves(p0_host)
    return {"losses": ref["losses"], "p0": p0, "p3": ref["p3"],
            "mu1": [g + weight_decay * p for g, p in zip(ref["g1"], p0)]}


def leaf_norms(run_like: dict, weight_decay: float) -> dict:
    """Losses, and each leaf's norm of the first gradient and of the change
    over the set-up steps, of a program-like record (``prog`` of
    ``run.first_steps`` or ``_as_program``)."""
    import numpy as np

    p0 = [np.asarray(x, np.float64) for x in run_like["p0"]]
    g1 = [np.asarray(m, np.float64) - weight_decay * p
          for m, p in zip(run_like["mu1"], p0)]
    d3 = [np.asarray(a, np.float64) - p for a, p in zip(run_like["p3"], p0)]
    return {"losses": [float(x) for x in run_like["losses"]],
            "g1": check._norms(g1).tolist(), "d3": check._norms(d3).tolist()}


def readings(cell: dict, seed: int, step, with_control: bool) -> dict:
    import jax

    from repro.optim import sgdm_init

    cfg, wl = cell["config"], cell["workload"]
    wd = cfg["weight_decay"]
    params, batch_of, k_ref, k_ctl = run.make_inputs(cell, seed)
    batches = [batch_of(j) for j in range(run.WARM_STEPS)]
    p0_host = run.host_copy(params)
    opt = jax.jit(sgdm_init)(params)
    if step is None:
        step = run.jit_program(cell)
    _, _, prog = run.first_steps(step, params, opt, batches, p0_host)
    del params, opt, batches
    gc.collect()
    num = run.reference_numerics(wl)
    ref = run.run_reference(cell, p0_host, batch_of, k_ref, num)
    out = {"seed": seed, "program": check.compare(prog, ref, wd)}
    norms = {"reference": leaf_norms(_as_program(ref, p0_host, wd), wd),
             "program": leaf_norms(prog, wd)}
    if with_control:
        ctl = run.run_reference(cell, p0_host, batch_of, k_ctl,
                                run.reference_numerics(wl, control=True))
        out["control"] = check.compare(_as_program(ctl, p0_host, wd), ref, wd)
        norms["control"] = leaf_norms(_as_program(ctl, p0_host, wd), wd)
        rows = wl.get("batch", cfg["batch"]) // 2
        half = run.run_reference(cell, p0_host, batch_of,
                                 jax.random.fold_in(k_ctl, 1), num, rows)
        out["half_batch"] = check.compare(_as_program(half, p0_host, wd),
                                          ref, wd)
        norms["half_batch"] = leaf_norms(_as_program(half, p0_host, wd), wd)
        frozen = run.run_reference(dict(cell, config=dict(cfg, lr=0.0)),
                                   p0_host, batch_of,
                                   jax.random.fold_in(k_ctl, 2), num)
        p0 = jax.tree.leaves(p0_host)
        out["unchanged"] = check.compare(
            {"losses": frozen["losses"], "p0": p0, "p3": p0,
             "mu1": [0.0 * p for p in p0]}, ref, wd)
    out["norms"] = norms
    return out, step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--control-seeds", default="", help="e.g. 1-3")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        run.check_device(cell["chips"])
    except run.BenchError as e:
        print(f"control: FAILED: {e}", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    with_control = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    step = None
    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        out, step = readings(cell, seed, step, seed in with_control)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
