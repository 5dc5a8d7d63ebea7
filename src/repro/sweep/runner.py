"""Convergence-proxy runner: one short training run per frontier cell.

CNN cells train the reduced paper models (``models/cnn.py``) on the
synthetic CIFAR stream with the paper's SGD-momentum recipe — the same
proxy ``benchmarks/table2_accuracy.py`` reports.  LM cells train the
reduced smoke configs of the assigned architectures (``models/lm.py``:
dense transformer / Mamba2 SSD / MoE) on the synthetic Markov token stream
with AdamW.  Everything is seeded from the cell, so a cell's metrics are
deterministic given the software stack — which is what lets the gate hold
tight per-cell tolerances against a committed baseline.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import QuantConfig
from repro.data import make_cifar_iterator, make_lm_iterator
from repro.models import lm
from repro.models.cnn import CNNConfig, init_cnn
from repro.optim import adamw_init, adamw_update, sgdm_init
from repro.train import make_cnn_train_step

from .grid import LM_ARCHS, Cell

__all__ = ["run_cell", "run_cells"]

_LM_LR = 1e-3
_NUM_CLASSES = 10

# A proxy has diverged when its trailing loss exceeds this multiple of the
# uniform-prediction loss (ln(classes) / ln(vocab)) — or goes non-finite.
_DIVERGENCE_MULT = 2.0


def _tail_mean(xs: list[float]) -> float:
    k = max(1, len(xs) // 5)
    return sum(xs[-k:]) / k


def _train_cnn(cell: Cell) -> tuple[float, float | None]:
    cfg = CNNConfig(arch=cell.arch, num_classes=_NUM_CLASSES,
                    width_mult=cell.width, in_hw=cell.hw)
    qcfg = None
    if cell.emformat is not None:
        qcfg = QuantConfig(fmt=cell.emformat, grouping=cell.grouping,
                           backend=cell.backend)
    params = init_cnn(jax.random.key(cell.seed), cfg)
    opt = sgdm_init(params)
    nxt, ds = make_cifar_iterator(batch=cell.batch, hw=cell.hw,
                                  num_classes=_NUM_CLASSES, seed=cell.seed)

    step = jax.jit(make_cnn_train_step(cfg, qcfg, cell.lr, rounding_seed=1))

    losses, accs = [], []
    for i in range(cell.steps):
        batch, ds = nxt(ds)
        params, opt, l, a = step(params, opt, batch, jnp.int32(i))
        losses.append(float(l))
        accs.append(float(a))
    return _tail_mean(losses), _tail_mean(accs)


def _train_lm(cell: Cell) -> tuple[float, float | None]:
    cfg = get_smoke_config(LM_ARCHS[cell.arch])
    cfg = dataclasses.replace(
        cfg,
        quant=cell.emformat is not None,
        fmt=cell.emformat if cell.emformat is not None else cfg.fmt,
        quant_backend=cell.backend,
    )
    p = lm.init_lm(jax.random.key(cell.seed), cfg)
    opt = adamw_init(p)
    extras = ()
    if cfg.frontend != "none" and cfg.family != "encdec":
        extras = (("frontend_emb",
                   (cell.batch, cfg.frontend_len, cfg.frontend_dim)),)
    nxt, ds = make_lm_iterator(cell.batch, cell.seq, cfg.vocab,
                               seed=cell.seed, extras=extras)

    @jax.jit
    def step(p, opt, batch, i):
        (l, _), g = jax.value_and_grad(lm.lm_loss, has_aux=True)(
            p, batch, cfg, jax.random.fold_in(jax.random.key(1), i))
        p, opt = adamw_update(g, opt, p, lr=_LM_LR)
        return p, opt, l

    losses = []
    for i in range(cell.steps):
        batch, ds = nxt(ds)
        p, opt, l = step(p, opt, batch, jnp.int32(i))
        losses.append(float(l))
    return _tail_mean(losses), None


def divergence_threshold(cell: Cell) -> float:
    if cell.is_cnn:
        return _DIVERGENCE_MULT * math.log(_NUM_CLASSES)
    return _DIVERGENCE_MULT * math.log(get_smoke_config(LM_ARCHS[cell.arch]).vocab)


def run_cell(cell: Cell) -> dict:
    """Train one cell; return its BENCH_accuracy.json row."""
    t0 = time.perf_counter()
    final_loss, final_acc = (_train_cnn if cell.is_cnn else _train_lm)(cell)
    wall = time.perf_counter() - t0
    diverged = (not math.isfinite(final_loss)
                or final_loss > divergence_threshold(cell))
    row = {
        "name": f"sweep/{cell.cell_id()}",
        "cell_id": cell.cell_id(),
        "config_hash": cell.config_hash(),
        "arch": cell.arch,
        "fmt": cell.fmt,
        "backend": cell.backend,
        "grouping": cell.grouping,
        "steps": cell.steps,
        "final_loss": round(final_loss, 6) if math.isfinite(final_loss) else None,
        "final_acc": None if final_acc is None else round(final_acc, 6),
        "diverged": bool(diverged),
        "wall_time_s": round(wall, 2),
    }
    if cell.envelope_acc is not None:
        row["envelope_acc"] = cell.envelope_acc
    if cell.envelope_loss is not None:
        row["envelope_loss"] = cell.envelope_loss
    return row


def run_cells(cells: list[Cell], verbose: bool = True) -> list[dict]:
    rows = []
    for i, cell in enumerate(cells):
        row = run_cell(cell)
        rows.append(row)
        if verbose:
            loss = row["final_loss"]
            acc = row["final_acc"]
            print(f"[{i + 1}/{len(cells)}] {row['cell_id']}: "
                  f"loss={'nan' if loss is None else f'{loss:.3f}'}"
                  + ("" if acc is None else f" acc={acc:.3f}")
                  + (" DIVERGED" if row["diverged"] else "")
                  + f" ({row['wall_time_s']:.1f}s)", flush=True)
    return rows
