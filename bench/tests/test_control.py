"""The controls of ``correct``, at the cell's own size, on the CPU.

In the plain cell's place this puts the reference: at the cell's own
precision (a sound run: correct), with half of each batch left out or its
state left unchanged (faults: not correct), and in the nearest precision
below the one the cell states (the control: not correct).  On the CPU the
cell's stated precision is float32 (``reference.XLA_DEFAULT``), so the
control is held against that.  A reference run takes seconds here."""
import jax
import pytest

from bench import check, control, run

SEED = 20260


def _readings(cell, num, key, rows=None, frozen=False):
    params, batch_of, k_ref, _ = run.make_inputs(cell, SEED)
    p0 = jax.device_get(params)
    ref = run.run_reference(cell, p0, batch_of, k_ref,
                            run.reference_numerics(cell["workload"]))
    cfg = cell["config"]
    if frozen:  # a step that returns its state unchanged
        cell = dict(cell, config=dict(cfg, lr=0.0))
    other = run.run_reference(cell, p0, batch_of, key, num, rows)
    prog = control._as_program(other, p0, cfg["weight_decay"])
    if frozen:
        prog.update(p3=prog["p0"], mu1=[0.0 * p for p in prog["p0"]])
    return check.compare(prog, ref, cfg["weight_decay"])


@pytest.fixture(scope="module")
def cell():
    return run.load_cell("resnet20-cifar.plain")


@pytest.fixture(scope="module")
def sound(cell):
    return _readings(cell, run.reference_numerics(cell["workload"]),
                     jax.random.key(7))


def test_sound_draw_is_correct(cell, sound):
    assert check.verdict(sound, cell["workload"]["limits"]), sound


def test_unchanged_state_is_not_correct(cell):
    nums = _readings(cell, run.reference_numerics(cell["workload"]),
                     jax.random.key(9), frozen=True)
    assert not check.verdict(nums, cell["workload"]["limits"]), nums


def test_control_is_not_correct(cell):
    nums = _readings(cell, run.reference_numerics(cell["workload"],
                                                  control=True),
                     jax.random.key(8))
    assert not check.verdict(nums, cell["workload"]["limits"]), nums


def test_half_batch_is_not_correct(cell):
    nums = _readings(cell, run.reference_numerics(cell["workload"]),
                     jax.random.key(9), rows=cell["config"]["batch"] // 2)
    assert not check.verdict(nums, cell["workload"]["limits"]), nums
