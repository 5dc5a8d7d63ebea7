"""The step is called as the program hands it out: a step the program has
jitted keeps the arguments it donates, an un-jitted step compiles as it
did under the benchmark's own ``jax.jit``, and a step that consumes its
batch fails the run closed."""
import jax
import numpy as np
import pytest

from bench import run


def _donating(monkeypatch, argnums, wrap=lambda step: step):
    """Make the program hand out its step already jitted, donating
    ``argnums``; return the list that collects each run's first
    parameters."""
    program_step = run.program_step
    make_inputs = run.make_inputs
    first = []

    def jitted(cell):
        return jax.jit(wrap(program_step(cell)), donate_argnums=argnums)

    def inputs(cell, seed):
        out = make_inputs(cell, seed)
        first.append(out[0])
        return out

    monkeypatch.setattr(run, "program_step", jitted)
    monkeypatch.setattr(run, "make_inputs", inputs)
    return first


def test_donating_step_is_called_as_handed_out(monkeypatch, tiny_cell):
    """The program's own jit, with its donation, runs the whole cell: the
    same numbers as the un-jitted step on the same seed, and the first
    parameters consumed, which an outer ``jax.jit`` would not do."""
    cell = tiny_cell("plain")
    plain = run.run_cell(cell, 11, 0.2, False, dev=jax.devices()[0])
    first = _donating(monkeypatch, (0, 1))
    donated = run.run_cell(cell, 11, 0.2, False, dev=jax.devices()[0])
    assert donated["correct"], donated["numbers"]
    assert donated["numbers"] == plain["numbers"]
    leaves = jax.tree.leaves(first[-1])
    assert leaves and all(x.is_deleted() for x in leaves)


def test_jitted_step_is_not_jitted_again(monkeypatch, tiny_cell):
    step = jax.jit(lambda *a: a, donate_argnums=(0, 1))
    monkeypatch.setattr(run, "program_step", lambda cell: step)
    assert run.jit_program(tiny_cell("plain")) is step


def test_plain_step_compiles_as_under_jax_jit(tiny_cell):
    """An un-jitted step compiles to the same HLO, metadata included, as
    the same step under ``jax.jit``, which is how the benchmark called
    every step before it called a jitted one as it is.  Both are lowered
    from one call site: the module's stack-frame table holds the caller's
    lines and columns."""
    from repro.optim import sgdm_init

    cell = tiny_cell("plain")
    params, batch_of, _, _ = run.make_inputs(cell, 5)
    opt = jax.jit(sgdm_init)(params)

    ours, plain = [step.lower(params, opt, batch_of(0),
                              np.int32(0)).compile().as_text()
                   for step in (run.jit_program(cell),
                                jax.jit(run.program_step(cell)))]
    assert "op_name=" in ours and ours == plain


def _returns_its_labels(step):
    """A step whose last result the batch's label buffer can hold, so that
    donating the batch consumes it."""
    def leaky(params, opt, batch, i):
        params, opt, loss, _ = step(params, opt, batch, i)
        return params, opt, loss, batch["label"] + 1
    return leaky


def test_step_that_consumes_its_batch_fails_closed(monkeypatch, tiny_cell):
    _donating(monkeypatch, (0, 1, 2), _returns_its_labels)
    with pytest.raises(run.BenchError, match="consumed its batch"):
        run.run_cell(tiny_cell("plain"), 13, 0.2, False,
                     dev=jax.devices()[0])
