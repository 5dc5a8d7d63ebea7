"""The comparison that decides ``correct`` for a training cell.

Set-up drives the compiled train step through its first three steps; the
reference (``reference.py``) follows the same three steps from the same
weights and batches.  The numbers read:

* ``loss1_gap`` / ``loss_gap``: the relative gap of the first step's loss
  (the forward pass alone), and the largest of the three steps';
* ``grad_gap_worst`` / ``grad_gap_median``: for the first gradient, as the
  optimizer got it (read back from the momentum after one step:
  ``mu1 - weight_decay * p0``), the worst and the median leaf's gap
  between the program's and the reference's norms;
* ``update_gap_worst`` / ``update_gap_median``: the same for the
  parameters' change over the three steps, over the leaves the reference's
  first gradient moves (norm at least a thousandth of the median leaf's;
  the others move by round-off alone).

A leaf's gap is ``| |a| - |b| |`` over the larger of the reference leaf's
norm and the median reference leaf's norm.  ``grad_shift`` /
``update_shift`` are the same quotient taken with its sign: the magnitude
of its median over the leaves, which rounding noise of either side moves
little and a systematic change of every leaf's norm moves much.  The cell's workload file
names the numbers it compares, each with its limit (``limits``).
"""
from __future__ import annotations

import math

import numpy as np

MOVED = 1e-3  # a leaf moves when its gradient norm is >= this x the median


def _norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(x, np.float64).ravel())
                     for x in leaves])


def _gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """Each kept leaf's signed gap (``nan`` where not kept)."""
    floor = np.median(ref[keep])
    gap = (prog - ref) / np.maximum(ref, floor)
    return np.where(keep, gap, np.nan)


def compare(prog: dict, ref: dict, weight_decay: float) -> dict:
    """Numbers of one run.

    ``prog``: ``losses`` (3), ``p0``, ``mu1`` and ``p3`` (leaf lists);
    ``ref``: ``losses`` (3), ``g1`` and ``p3``.  Also returns the worst
    leaves' indices under ``worst``.
    """
    p0 = [np.asarray(x, np.float64) for x in prog["p0"]]
    g_prog = _norms(np.asarray(m, np.float64) - weight_decay * p
                    for m, p in zip(prog["mu1"], p0))
    g_ref = _norms(ref["g1"])
    moved = g_ref >= MOVED * np.median(g_ref)
    d_prog = _norms(np.asarray(a, np.float64) - p
                    for a, p in zip(prog["p3"], p0))
    d_ref = _norms(np.asarray(a, np.float64) - p
                   for a, p in zip(ref["p3"], p0))
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"])]
    grad = _gaps(g_prog, g_ref, np.ones_like(moved))
    update = _gaps(d_prog, d_ref, moved)
    return {"loss1_gap": losses[0], "loss_gap": max(losses),
            "grad_gap_worst": float(np.nanmax(np.abs(grad))),
            "grad_gap_median": float(np.nanmedian(np.abs(grad))),
            "grad_shift": float(abs(np.nanmedian(grad))),
            "update_gap_worst": float(np.nanmax(np.abs(update))),
            "update_gap_median": float(np.nanmedian(np.abs(update))),
            "update_shift": float(abs(np.nanmedian(update))),
            "worst": {"loss_step": int(np.argmax(losses)) + 1,
                      "grad_leaf": int(np.nanargmax(np.abs(grad))),
                      "update_leaf": int(np.nanargmax(np.abs(update))),
                      "unmoved_leaves": int(np.sum(~moved))}}


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number the limits name finite and within its limit."""
    return bool(limits) and all(
        math.isfinite(numbers[k]) and numbers[k] <= limit
        for k, limit in limits.items())
