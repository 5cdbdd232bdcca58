"""The benchmark's byte and FLOP counts against the program's and against
hand counts."""

import jax
import numpy as np
import pytest

from bench import counts, harness
from repro.kernels import ops


def _leaf_sizes(config: str):
    cfg = harness.load_json("configs", config)
    model = harness.load_module("configs", config)
    shapes = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return [int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)]


@pytest.mark.parametrize("config, params, maskable", [
    ("gru_lm_wikitext2", 4_374_656, 4_374_272),
])
def test_counts_equal_the_programs_byte_model(config, params, maskable):
    sizes = _leaf_sizes(config)
    assert sum(sizes) == params
    n = counts.maskable_elements(sizes, 256)
    assert n == maskable
    for kw in (dict(fused=True, quantize=False, assume_masked=True),
               dict(fused=True, quantize=True),
               dict(fused=False, quantize=False),
               dict(fused=True, quantize=False, wire="bitmap")):
        assert counts.wirepath_bytes_moved(n, 0.5, **kw) == \
            ops.wirepath_bytes_moved(n, 0.5, **kw)


def test_mask_kernel_bytes_by_hand():
    n = 4_374_272
    # histogram + 2 counts (3 reads), apply read + write, encode read,
    # encode writes of f32 values and a 1-bit keep map.
    want = 4 * n * 3 + 4 * n * 2 + 4 * n + 4 * n + (n + 7) // 8
    assert counts.mask_kernel_bytes(n, 0.5, codec_fused=True) == want
    assert counts.mask_kernel_bytes(n, 0.5, codec_fused=False) == 4 * n * 5


def test_gru_flops_by_hand():
    cfg = harness.load_json("configs", "gru_lm_wikitext2")
    model = harness.load_module("configs", "gru_lm_wikitext2")
    # three gates 3 * 2 * 256 * 128, projection 2 * 128 * 128, tied
    # logits 2 * 128 * 33,278; backward twice the forward.
    assert model.token_flops(cfg) == 3 * (196_608 + 32_768 + 8_519_168)
    assert model.example_flops(cfg, {"seq_len": 32}) == 839_860_224
