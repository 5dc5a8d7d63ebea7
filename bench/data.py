"""Synthetic CIFAR-like training batches, made on the device from a seed.

The arithmetic is that of the program's ``cifar_like_batch`` generator
(class-conditional sine patterns plus Gaussian noise), computed for the
drawn labels only, so that 1000 classes at 224x224 need no table of every
class's pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _pattern(labels: jax.Array, hw: int) -> jax.Array:
    """(B, 3, hw, hw) sine patterns of the given classes."""
    ys, xs = jnp.mgrid[0:hw, 0:hw] / hw
    fx = 1.0 + (labels % 5).astype(jnp.float32)
    fy = 1.0 + (labels // 5 % 5).astype(jnp.float32)
    phase = labels.astype(jnp.float32) * 0.7
    pat = jnp.sin(2 * jnp.pi * (fx[:, None, None] * xs + fy[:, None, None] * ys)
                  + phase[:, None, None])
    return jnp.stack([pat, jnp.roll(pat, hw // 4, axis=-1), -pat], axis=1)


def batch(key: jax.Array, size: int, hw: int, num_classes: int,
          noise: float = 0.6) -> dict[str, jax.Array]:
    kl, kn = jax.random.split(key)
    labels = jax.random.randint(kl, (size,), 0, num_classes)
    x = _pattern(labels, hw) + noise * jax.random.normal(kn, (size, 3, hw, hw))
    return {"image": x.astype(jnp.float32), "label": labels}

