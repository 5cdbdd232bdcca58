"""The traffic generators are deterministic in the seed and have the
stated shapes."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


def _gen(name, cfg, traffic, seed):
    mod = harness.load_module("traffic", name)
    return mod.generate(jax.random.PRNGKey(seed), cfg, traffic)


def test_markov_text():
    cfg = {"vocab_size": 300}
    traffic = {"num_clients": 4, "train_tokens": 4 * 12 * 2 * 8 + 5,
               "batch": 2, "seq_len": 8}
    (x, y), n = _gen("markov_text", cfg, traffic, 3)
    assert x.shape == y.shape == (4, 12, 2, 8) and x.dtype == jnp.int32
    assert np.all(np.asarray(n) == 24)
    assert int(x.min()) >= 0 and int(x.max()) < 300
    # targets are the inputs shifted by one token within each window
    np.testing.assert_array_equal(np.asarray(x)[..., 1:],
                                  np.asarray(y)[..., :-1])
    (x2, y2), _ = _gen("markov_text", cfg, traffic, 3)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))
    (x3, _), _ = _gen("markov_text", cfg, traffic, 4)
    assert not np.array_equal(np.asarray(x), np.asarray(x3))
    # a Zipf-biased vocabulary: low ids are drawn more often than high ids
    big = dict(traffic, num_clients=8, train_tokens=20_001)
    (xb, _), _ = _gen("markov_text", cfg, big, 0)
    xb = np.asarray(xb).ravel()
    assert (xb < 150).mean() > 0.6
