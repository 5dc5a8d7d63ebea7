"""The reference against the program's own oracles, on the CPU: the
reference imports nothing of the program, so these tests are where the
two are held to the same semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R


@pytest.mark.parametrize("fmt,shape", [((2, 1), (64, 256)), ((2, 4), (40, 200)),
                                       ((2, 1), (8, 36))])
def test_mls_quantizer_matches_program_oracle(fmt, shape):
    from repro.core.formats import EMFormat
    from repro.kernels.ref import decode_frac_int, quantize_ref

    em = EMFormat(*fmt)
    x = jax.random.normal(jax.random.key(1), shape) * 3.0
    kp = -(-shape[1] // 128) * 128
    xp = jnp.pad(x, ((0, 0), (0, kp - shape[1])))
    codes, sg, st = quantize_ref(xp, em, 128, r_u8=None)
    f = decode_frac_int(codes, em).astype(jnp.float32)
    unit = 2.0 ** (em.e_min - em.m)
    theirs = (f.reshape(shape[0], -1, 128) * sg[:, :, None] * st
              * unit).reshape(shape[0], kp)[:, :shape[1]]
    ours = R.mls_rows(x, R.Numerics(fmt=fmt, stochastic=False), None)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_stochastic_rounding_is_unbiased_and_on_grid():
    # tensor and group scale 1 (the first column); 0.3 lies between the
    # <2,1> grid points 0.25 and 0.375
    num = R.Numerics(fmt=(2, 1))
    y = jnp.concatenate([jnp.ones((4, 1)), jnp.full((4, 127), 0.3)], 1)
    draws = jnp.stack([R.mls_rows(y, num, jax.random.key(i))
                       for i in range(400)])
    assert float(jnp.mean(draws[:, :, 1:])) == pytest.approx(0.3, rel=0.02)
    assert set(np.unique(np.asarray(draws[:, :, 1:]))) <= {0.25, 0.375}


def test_round_significant_matches_bfloat16():
    x = jax.random.normal(jax.random.key(3), (1000,)) * 100
    ours = R.round_significant(x, 8, None)
    theirs = x.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_plain_step_matches_program_on_cpu(tiny_cell):
    """At float32 on the CPU the plain reference and the program's step
    agree to rounding."""
    from bench import run

    rec = run.run_cell(tiny_cell("plain"), 11, 0.1, False,
                       dev=jax.devices()[0])
    assert rec["numbers"]["update_gap_worst"] < 1e-4
    assert rec["numbers"]["loss_gap"] < 1e-5
