"""The plain reference's pieces: the exact k-th magnitude and the seeded
cohorts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kth_largest_equals_a_sort(dtype):
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (600, 7))
                ).astype(dtype)
    x = x.at[0, :3].set(0).at[1, :2].set(x[2, 0])        # zeros and ties
    for k in (1, 2, 5, 2100, 4199, 4200):
        want = jnp.sort(x.ravel())[x.size - k]
        assert reference._kth_largest(x, k) == want, k


def test_cohorts_are_the_servers():
    from repro.core.federated import cohort_select
    from repro.core.sampling import StaticSampling

    seed, M, rate = 2**31 + 5, 40, 0.25
    m = reference.cohort_size(rate, M, 2)
    assert m == 10
    key = jax.random.PRNGKey(seed)
    for ids in reference.cohorts(seed, M, m, 3):
        key, sub = jax.random.split(key)
        sample_key, _ = jax.random.split(sub)
        got, valid = cohort_select(sample_key, StaticSampling(rate, 2), 1.0,
                                   M, 16)
        np.testing.assert_array_equal(np.asarray(got)[np.asarray(valid) > 0],
                                      ids)


def test_diff_gaps_see_values_at_the_wrong_indices():
    r = {"a": jax.random.normal(jax.random.PRNGKey(1), (64, 8)),
         "b": jax.random.normal(jax.random.PRNGKey(2), (300,))}
    p = jax.tree.map(lambda x: jnp.roll(x.ravel(), 1).reshape(x.shape), r)
    keep = np.array([True, True])
    np.testing.assert_allclose(reference.norm_gaps(p, r, keep), 0, atol=1e-6)
    d = reference.diff_gaps(p, r, keep)
    assert np.all(d > 1.0), d
    assert np.all(reference.diff_gaps(r, r, keep) == 0)

