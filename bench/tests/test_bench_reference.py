"""The benchmark's plain reference agrees with the repository's oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from repro.core.stencil_spec import get
from repro.kernels import ref


@pytest.mark.parametrize("config,shape,steps", [
    ("j2d5pt", (40, 56), 7),
    ("j3d7pt", (12, 10, 24), 5),
])
def test_reference_matches_oracle(config, shape, steps):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    x = jax.random.uniform(jax.random.PRNGKey(1), shape, jnp.float32)
    got = reference.run(x, reference.taps_of(cfg), steps)
    want = ref.reference(x, get(cfg["stencil"]), steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-7)


def test_shift_reads_zero_outside_the_domain():
    x = jnp.arange(1.0, 7.0).reshape(2, 3)
    np.testing.assert_array_equal(reference.shifted(x, (0, 1)),
                                  [[2, 3, 0], [5, 6, 0]])
    np.testing.assert_array_equal(reference.shifted(x, (-1, 0)),
                                  [[0, 0, 0], [1, 2, 3]])


def test_max_abs_err_sees_nan():
    a = jnp.zeros((4, 4))
    assert float(reference.max_abs_err(a, a.at[1, 1].set(0.5))) == 0.5
    assert np.isnan(float(reference.max_abs_err(a, a.at[1, 1].set(
        jnp.nan))))
