"""A whole run, chip look skipped, with the timed path broken underneath:
each fault a training cell can have must make ``correct`` false, at a
size on the CPU at which the unbroken run is correct.  The MLS cell is not
in the benchmark (``PERF.md``, Open questions), so only the plain cell's
faults are held here."""
import jax
import pytest

from bench import run


def _unchanged(step):
    """A step that returns its state unchanged."""
    def broken(params, opt, batch, i):
        return (params, opt) + tuple(step(params, opt, batch, i)[2:])
    return broken


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(params, opt, batch, i):
        half = batch["label"].shape[0] // 2
        return step(params, opt, {k: v[:half] for k, v in batch.items()}, i)
    return broken


def test_sound_run_is_correct(tiny_cell):
    rec = run.run_cell(tiny_cell("plain"), 7, 0.2, False,
                       dev=jax.devices()[0])
    assert rec["correct"], rec["numbers"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(tiny_cell, fault):
    rec = run.run_cell(tiny_cell("plain"), 7, 0.2, False,
                       dev=jax.devices()[0], step_wrapper=fault)
    assert not rec["correct"], rec["numbers"]
