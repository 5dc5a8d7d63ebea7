"""End-to-end training: loss decreases under MLS quantization; restart from
checkpoint reproduces the exact continuation (deterministic SR streams)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import RunConfig, SHAPES
from repro.data import make_lm_iterator
from repro.models import lm
from repro.train import CheckpointManager, StragglerMonitor, make_train_step


def _mini_run(arch="glm4-9b", steps=8, microbatch=0):
    cfg = dataclasses.replace(get_smoke_config(arch), vocab=128)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], microbatch=microbatch,
                    optimizer="adamw", lr=1e-2)
    train_step, opt_init = make_train_step(run)
    step = jax.jit(train_step)
    params = lm.init_lm(jax.random.key(0), cfg)
    opt = opt_init(params)
    nxt, dstate = make_lm_iterator(batch=8, seq=32, vocab=cfg.vocab)
    losses = []
    for _ in range(steps):
        batch, dstate = nxt(dstate)
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    return cfg, run, params, opt, dstate, losses


def test_loss_decreases_quantized():
    _, _, _, _, _, losses = _mini_run(steps=25)
    best = min(losses[-5:])
    assert best < losses[0] - 0.5, losses


def test_microbatch_equivalence():
    """Gradient accumulation changes memory, not semantics (same data)."""
    cfg = get_smoke_config("chatglm3-6b")
    run0 = RunConfig(model=cfg, shape=SHAPES["train_4k"], microbatch=0, lr=1e-2)
    run4 = dataclasses.replace(run0, microbatch=4)
    s0, oi0 = make_train_step(run0)
    s4, oi4 = make_train_step(run4)
    params = lm.init_lm(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)}
    p0, _, m0 = jax.jit(s0)(params, oi0(params), batch)
    p4, _, m4 = jax.jit(s4)(params, oi4(params), batch)
    # stochastic rounding keys differ per microbatch layout; compare loosely
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max() /
                                        (jnp.abs(a).max() + 1e-9)), p0, p4)
    assert max(jax.tree.leaves(d)) < 0.35
    assert abs(float(m0["loss"]) - float(m4["loss"])) < 0.2


def test_checkpoint_restart_bitexact(tmp_path):
    cfg, run, params, opt, dstate, _ = _mini_run(steps=4)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(4, {"params": params, "opt": opt, "data": dstate})

    train_step, _ = make_train_step(run)
    step = jax.jit(train_step)
    nxt, _ = make_lm_iterator(batch=8, seq=32, vocab=cfg.vocab)

    # continue directly
    p_a, o_a, d_a = params, opt, dstate
    for _ in range(3):
        b, d_a = nxt(d_a)
        p_a, o_a, _ = step(p_a, o_a, b)

    # restore and continue
    r = mgr.restore({"params": params, "opt": opt, "data": dstate})
    p_b, o_b, d_b = r["params"], r["opt"], r["data"]
    for _ in range(3):
        b, d_b = nxt(d_b)
        p_b, o_b, _ = step(p_b, o_b, b)

    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_monitor_flags_slow_steps():
    import time

    mon = StragglerMonitor(warmup_steps=1, threshold=1.5)
    for i in range(6):
        mon.start()
        time.sleep(0.02 if i != 4 else 0.12)
        mon.stop()
    rep = mon.report()
    assert 5 in rep["straggler_steps"], rep


# ---------------------------------------------------------------------------
# persistent compilation cache: one fixed directory
# ---------------------------------------------------------------------------
def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    import pathlib

    from repro.train import compile_cache, enable_compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert got == str(repo / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == got
        assert enable_compile_cache() == got  # the same path on every run
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    from repro.train import compile_cache, enable_compile_cache

    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


# ---------------------------------------------------------------------------
# the shared CNN train step
# ---------------------------------------------------------------------------
def test_cnn_train_step_loss_and_schedule():
    """The builder's loss is the softmax cross-entropy of ``apply_cnn`` and
    a schedule is read at the optimizer's step count."""
    from repro.data import make_cifar_iterator
    from repro.models.cnn import CNNConfig, apply_cnn, init_cnn
    from repro.optim import sgdm_init
    from repro.train import make_cnn_train_step

    cfg = CNNConfig(arch="resnet20", width_mult=0.25, in_hw=8)
    params = init_cnn(jax.random.key(0), cfg)
    opt = sgdm_init(params)
    batch, _ = make_cifar_iterator(batch=4, hw=8)[0](
        make_cifar_iterator(batch=4, hw=8)[1])
    seen = []

    def lr_fn(count):
        seen.append(count)
        return 0.05

    # the step consumes ``params`` and ``opt``: read what is checked first
    logits = apply_cnn(params, batch["image"], cfg, None)
    ll = jax.nn.log_softmax(logits)
    want = -jnp.take_along_axis(ll, batch["label"][:, None], 1).mean()
    old_count = int(opt.step)
    old_params = jax.tree.map(np.array, params)

    step = make_cnn_train_step(cfg, None, lr_fn, rounding_seed=3)
    new_params, new_opt, loss, acc = step(params, opt, batch, jnp.int32(0))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert 0.0 <= float(acc) <= 1.0
    assert seen and int(new_opt.step) == old_count + 1
    moved = jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(np.any(a != np.asarray(b))), old_params, new_params))
    assert any(moved)
