"""The yardstick: the plain reference and the work counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import work
from bench.tests import helpers


def numpy_steps(x, taps, steps):
    x = np.asarray(x, np.float64)
    for _ in range(steps):
        acc = np.zeros_like(x)
        for off, c in taps:
            v = x
            for axis, o in enumerate(off):
                v = np.roll(v, -o, axis=axis)
            acc += c * v
        x = acc
    return x


@pytest.mark.parametrize("taps,shape,steps", [
    (helpers.TAPS_2D5P, (48, 64), 7),
    (helpers.TAPS_1D3P, (1000,), 13),
])
def test_reference_matches_numpy_in_float64(taps, shape, steps):
    taps = work.taps_from_config({"taps": taps})
    x = jax.random.normal(jax.random.key(1), shape, jnp.float32)
    got = work.reference_steps(x, taps, steps)
    want = numpy_steps(x, taps, steps)
    assert float(work.rel_err(got, jnp.asarray(want, jnp.float32))) < 1e-6


def test_bfloat16_reference_is_far_from_float32():
    taps = work.taps_from_config({"taps": helpers.TAPS_2D5P})
    x = jax.random.normal(jax.random.key(2), (64, 128), jnp.float32)
    hi = work.reference_steps(x, taps, 8)
    lo = work.reference_steps(x, taps, 8, "bfloat16")
    assert lo.dtype == jnp.float32
    assert float(work.rel_err(lo, hi)) > 1e-3


def test_least_counts():
    t2 = work.taps_from_config({"taps": helpers.TAPS_2D5P})
    t1 = work.taps_from_config({"taps": helpers.TAPS_1D3P})
    assert work.least_ops_per_point(t2) == 2 + 4     # 2 coefficients, 4 adds
    assert work.least_ops_per_point(t1) == 2 + 2
    assert work.least_ops_per_call(t2, (8, 16), 3) == 6 * 128 * 3
    assert work.least_bytes_per_call((8, 16), 4) == 2 * 128 * 4


def test_two_plans_of_one_problem_get_one_work_count():
    """The work is the problem's: a k=2 plan and a k=4, ttile=4 plan both
    produce the reference's answer, and the count takes no plan."""
    from repro.core.api import StencilPlan, StencilProblem
    taps = work.taps_from_config({"taps": helpers.TAPS_2D5P})
    shape, steps = (64, 256), 16
    prob = StencilProblem("2d5p", shape)
    x = jax.random.normal(jax.random.key(3), shape, jnp.float32)
    ref = work.reference_steps(x, taps, steps)
    for k, ttile in ((2, 1), (4, 4)):
        plan = StencilPlan(scheme="transpose", k=k, backend="pallas",
                           sweep="resident", ttile=ttile, vl=128, m=2, t0=8)
        assert float(work.rel_err(prob.run(x, steps, plan), ref)) < 1e-6
    count = work.least_ops_per_call(taps, shape, steps)
    assert count == 6 * 64 * 256 * 16
