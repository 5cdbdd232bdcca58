"""Compile-only checks of the Pallas masking kernels for a TPU v5e.

The CPU tests run every kernel in interpret mode, which never lowers it
through Mosaic, the TPU kernel compiler.  These tests compile the public
entry points with ``interpret=False`` for a *described* v5e (no chip
attached), at the paper models' real widths: the GRU LM's 33,278 x 128
tied embedding (WikiText-2 vocabulary) plus the 32x32 VGG tree.  What the
chip's compiler refuses (unaligned slices, scalar VMEM stores, casts it
cannot lower, more scoped VMEM than a kernel may use) fails here at no chip
time.  Each test asserts that the kernels survived as ``tpu_custom_call``s
in the compiled program.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codecs import FusedSparseCodec
from repro.kernels import ops
from repro.models.paper_models import init_gru_lm, init_vgg

WIKITEXT2_VOCAB = 33278
GAMMA = 0.1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)


@pytest.fixture(scope="module")
def delta_tree(one_chip):
    """Shapes of one client delta: the GRU LM at WikiText-2's vocabulary
    and the 32x32 VGG, as one pytree."""
    key = jax.random.PRNGKey(0)
    return _on(one_chip, {
        "gru": jax.eval_shape(lambda: init_gru_lm(key, WIKITEXT2_VOCAB)),
        "vgg": jax.eval_shape(lambda: init_vgg(key, image_size=32)),
    })


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def test_topk_mask_pytree_compiles_for_v5e(delta_tree):
    n = _custom_calls(
        lambda t: ops.topk_mask_pytree(t, GAMMA, interpret=False), delta_tree)
    assert n >= 1
    # one pallas_call per HBM sweep of the analytic model
    assert n == ops.pytree_sweep_count(len(jax.tree.leaves(delta_tree)))


def test_topk_mask_pytree_compiles_for_v5e_under_vmap(one_chip):
    """The round masks a whole cohort at once: the kernels under vmap."""
    key = jax.random.PRNGKey(0)
    gru = jax.eval_shape(lambda: init_gru_lm(key, WIKITEXT2_VOCAB))
    cohort = _on(one_chip, jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct((2, *leaf.shape), leaf.dtype), gru))
    n = _custom_calls(
        jax.vmap(lambda t: ops.topk_mask_pytree(t, 0.5, interpret=False)),
        cohort)
    assert n >= 1


# The COO wire's compaction stage (seg.segmented_compact): one kernel
# launch for every COO leaf of the tree, on top of the HBM sweeps.
COMPACT_STAGE = 1


@pytest.mark.parametrize("wire", ["coo", "bitmap"])
@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
def test_topk_encode_pytree_compiles_for_v5e(delta_tree, wire, quantize):
    n = _custom_calls(
        lambda t: ops.topk_encode_pytree(t, GAMMA, wire=wire,
                                         quantize=quantize, interpret=False),
        delta_tree)
    assert n >= 1
    compact = COMPACT_STAGE if wire == "coo" else 0
    assert n == ops.wirepath_sweep_count(fused=True,
                                         quantize=quantize) + compact


@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
def test_coo_compaction_compiles_for_v5e_under_cohort_vmap(one_chip,
                                                           quantize):
    """The codec's position in the round: the GRU embedding's COO wire at
    gamma 0.5 for a 32-client cohort, compacted by one kernel launch with
    no sort left in the program."""
    emb = jax.ShapeDtypeStruct((32, WIKITEXT2_VOCAB, 128), jnp.float32,
                               sharding=one_chip)
    text = jax.jit(jax.vmap(lambda t: ops.topk_encode_pytree(
        {"embed": t}, 0.5, quantize=quantize, assume_masked=True,
        interpret=False))).lower(emb).compile().as_text()
    assert text.count("tpu_custom_call") == ops.wirepath_sweep_count(
        fused=True, assume_masked=True, quantize=quantize) + COMPACT_STAGE
    assert "fl_seg_compact" in text
    assert " sort(" not in text


def test_coo_roundtrip_compiles_for_v5e_under_cohort_vmap(one_chip):
    """Both halves of the fused COO wire in the round: the GRU embedding
    at gamma 0.5 for a 32-client cohort, compacted and expanded by
    kernels, with no sort and no scatter left in the program."""
    emb = jax.ShapeDtypeStruct((32, WIKITEXT2_VOCAB, 128), jnp.float32,
                               sharding=one_chip)
    codec = FusedSparseCodec(gamma=0.5, interpret=False)
    text = jax.jit(jax.vmap(codec.roundtrip)).lower(emb).compile().as_text()
    assert "fl_seg_compact" in text
    assert "fl_seg_expand" in text
    assert " sort(" not in text
    assert " scatter(" not in text


@pytest.mark.parametrize("leaf", [("gru", "embed"), ("vgg", "conv3b", "w")],
                         ids=["gru_embed", "vgg_conv3b"])
def test_topk_mask_per_leaf_compiles_for_v5e(delta_tree, leaf):
    x = delta_tree
    for k in leaf:
        x = x[k]
    n = _custom_calls(lambda a: ops.topk_mask(a, GAMMA, interpret=False), x)
    assert n >= 1

