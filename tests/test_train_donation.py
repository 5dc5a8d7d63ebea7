"""The CNN train step donates its parameters and optimizer state.

``make_cnn_train_step`` hands out a jitted step whose outputs reuse the
buffers of the ``params`` and ``opt`` it is given, so a call allocates no
new buffer for the state.  The batch and the step index are not donated:
callers reuse batches.  Donation moves where XLA writes its outputs, never
what it computes.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FMT_CIFAR, QuantConfig
from repro.data import make_cifar_iterator
from repro.models.cnn import CNNConfig, init_cnn
from repro.optim import sgdm_init
from repro.train import make_cnn_train_step

CFG = CNNConfig(arch="resnet20", width_mult=0.25, in_hw=8)
QCFGS = {"plain": None,
         "mls": QuantConfig(fmt=FMT_CIFAR, backend="fake_quant")}


def _inputs():
    params = init_cnn(jax.random.key(0), CFG)
    nxt, ds = make_cifar_iterator(batch=4, hw=8)
    batch, _ = nxt(ds)
    return params, sgdm_init(params), batch


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


@pytest.mark.parametrize("numerics", sorted(QCFGS))
def test_step_consumes_params_and_opt_not_batch(numerics):
    params, opt, batch = _inputs()
    step = make_cnn_train_step(CFG, QCFGS[numerics], 0.05, rounding_seed=1)
    i = jnp.int32(0)
    out = step(params, opt, batch, i)
    jax.block_until_ready(out)
    state = jax.tree.leaves((params, opt))
    assert state and all(x.is_deleted() for x in state)
    assert not any(x.is_deleted() for x in jax.tree.leaves((batch, i)))


def test_compiled_step_aliases_every_state_leaf():
    """Every leaf of ``params`` and ``opt`` (the step's first parameters,
    in tree order) is aliased to an output; no other parameter is."""
    params, opt, batch = _inputs()
    step = make_cnn_train_step(CFG, None, 0.05, rounding_seed=1)
    hlo = step.lower(params, opt, batch, jnp.int32(0)).compile().as_text()
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    # input_output_alias={ {<output>}: (<parameter>, {}, may-alias), ... }
    aliased = [int(n) for n in re.findall(r"\{\d+\}: \((\d+), \{\}", header)]
    n_state = len(jax.tree.leaves((params, opt)))
    assert sorted(aliased) == list(range(n_state))


@pytest.mark.parametrize("numerics", sorted(QCFGS))
def test_donation_changes_no_number(numerics):
    """The donating step's results equal, bit for bit, those of the same
    step under an outer ``jax.jit``, which donates nothing."""
    params, opt, batch = _inputs()
    step = make_cnn_train_step(CFG, QCFGS[numerics], 0.05, rounding_seed=1)
    kept_params, kept_opt = _copy(params), _copy(opt)
    want = jax.jit(step)(kept_params, kept_opt, batch, jnp.int32(2))
    assert not any(x.is_deleted()
                   for x in jax.tree.leaves((kept_params, kept_opt)))
    got = step(params, opt, batch, jnp.int32(2))
    want, got = jax.device_get((want, got))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w)
