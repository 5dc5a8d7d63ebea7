"""Optimizers (fp32 state) + schedules.

The paper keeps the weight update in full precision (Alg. 1 l.13 and
Table VI "SGD Update" rows): master weights, momenta and the update itself
are fp32 regardless of the low-bit conv/GEMM format.

* ``sgdm``  — SGD + momentum + weight decay (the paper's CNN recipe:
  momentum 0.9, wd 5e-4, step-decayed lr).
* ``adamw`` — decoupled weight decay Adam (LM runs).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jax.Array
    mu: Any  # first moment / momentum (pytree, fp32)
    nu: Any  # second moment (pytree, fp32; () leaves for sgdm)


def _f32(tree):
    return jax.tree.map(lambda p: p.astype(jnp.float32), tree)


def clip_by_global_norm(grads, max_norm: float):
    gn = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


# ---------------------------------------------------------------------------
# SGD + momentum (paper CNN recipe)
# ---------------------------------------------------------------------------
def sgdm_init(params) -> OptState:
    return OptState(jnp.int32(0), jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params), ())


def sgdm_update(grads, state: OptState, params, lr, momentum=0.9, weight_decay=5e-4):
    def upd(g, m, p):
        g = g.astype(jnp.float32) + weight_decay * p.astype(jnp.float32)
        m = momentum * m + g
        return m, (p.astype(jnp.float32) - lr * m).astype(p.dtype)

    with jax.named_scope("optimizer"):
        flat = jax.tree.map(upd, grads, state.mu, params)
        mu = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
        new_p = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
        return new_p, OptState(state.step + 1, mu, ())


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def adamw_init(params) -> OptState:
    z = lambda: jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return OptState(jnp.int32(0), z(), z())


def adamw_update(grads, state: OptState, params, lr, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1):
    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        newp = p.astype(jnp.float32) * (1.0 - lr * weight_decay) - lr * u
        return m, v, newp.astype(p.dtype)

    with jax.named_scope("optimizer"):
        t = state.step + 1
        c1 = 1.0 - b1 ** t.astype(jnp.float32)
        c2 = 1.0 - b2 ** t.astype(jnp.float32)
        flat = jax.tree.map(upd, grads, state.mu, state.nu, params)
        is3 = lambda t: isinstance(t, tuple)
        mu = jax.tree.map(lambda t: t[0], flat, is_leaf=is3)
        nu = jax.tree.map(lambda t: t[1], flat, is_leaf=is3)
        new_p = jax.tree.map(lambda t: t[2], flat, is_leaf=is3)
        return new_p, OptState(t, mu, nu)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------
def step_decay_schedule(base_lr: float, boundaries, factor=0.1):
    """Paper: lr/10 at epochs 80/120 (CIFAR) or every 30 epochs (ImageNet)."""

    def lr(step):
        step = jnp.asarray(step)
        mult = jnp.float32(1.0)
        for b in boundaries:
            mult = mult * jnp.where(step >= b, factor, 1.0)
        return base_lr * mult

    return lr


def cosine_schedule(base_lr: float, warmup: int, total: int, min_frac=0.1):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def make_optimizer(name: str, **kw) -> tuple[Callable, Callable]:
    if name == "sgdm":
        return sgdm_init, lambda g, s, p, lr: sgdm_update(g, s, p, lr, **kw)
    if name == "adamw":
        return adamw_init, lambda g, s, p, lr: adamw_update(g, s, p, lr, **kw)
    raise ValueError(name)
