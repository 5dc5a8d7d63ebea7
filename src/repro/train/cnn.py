"""CNN classifier train step: the paper's CIFAR recipe (Sec. VI-A).

Forward through :func:`repro.models.cnn.apply_cnn` (quantized per the
layer's :class:`QuantConfig`, first conv and classifier in fp32), softmax
cross-entropy, ``value_and_grad``, and the SGD-momentum update.  One
builder serves the example trainer, the frontier sweep and the chip smoke
run, so all three train the same program.
"""
from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core import QuantConfig
from repro.models.cnn import CNNConfig, apply_cnn
from repro.optim import sgdm_update

__all__ = ["make_cnn_train_step"]


def make_cnn_train_step(
    cfg: CNNConfig,
    qcfg: QuantConfig | None,
    lr: float | Callable,
    *,
    rounding_seed: int,
):
    """Build ``step(params, opt, batch, i) -> (params, opt, loss, acc)``.

    ``lr`` is a constant or a schedule of the optimizer step count.  The
    stochastic-rounding key of step ``i`` is ``fold_in(key(rounding_seed),
    i)``.

    The step comes back jitted, donating ``params`` and ``opt``: a call
    consumes the ones it is given (their buffers hold the new state), so
    callers rebind both to its outputs.  The batch and ``i`` are not
    donated.  Call it as it is, or lower it ahead of time with
    ``step.lower(...)``; an outer ``jax.jit`` around it drops the donation
    (JAX 0.9.0).
    """

    def loss_fn(params, batch, i):
        key = jax.random.fold_in(jax.random.key(rounding_seed), i)
        logits = apply_cnn(params, batch["image"], cfg, qcfg, key)
        with jax.named_scope("loss"):
            ll = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(ll, batch["label"][:, None], 1).mean()
            acc = (logits.argmax(-1) == batch["label"]).mean()
        return loss, acc

    def step(params, opt, batch, i):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, i)
        with jax.named_scope("optimizer"):
            lr_t = lr(opt.step) if callable(lr) else lr
        params, opt = sgdm_update(grads, opt, params, lr_t)
        return params, opt, loss, acc

    return jax.jit(step, donate_argnums=(0, 1))
