"""The COO compaction stage of the fused wire path (DESIGN.md §10).

``seg.segmented_compact`` packs the fused encode sweep's outputs into each
leaf's k-slot COO payload with a shift network and block writes, where the
reference (``ops.coo_compact_reference``) takes a cumulative sum and two
scatters.  The wire must not move by a byte: every case below builds the
payload through the real entry point twice, once with the compaction stage
swapped for the reference, and compares indices, values and shape.

Leaves are built with a chosen number of nonzeros and encoded with
``assume_masked=True``, which ships every nonzero: so a case sets each
leaf's kept count against its budget k = round(0.5 * size) exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codecs import FusedSparseCodec
from repro.kernels import ops
from repro.kernels import segmented as seg

GAMMA = 0.5
SIZES = [1023, 1024, 1025, 3 * 1024 + 7]


def _kept(size: int, rule: str) -> int:
    k = max(1, round(GAMMA * size))
    return {"none": 0, "under": k - max(1, k // 8), "exact": k,
            "over": min(size, k + 300), "all": size}[rule]


def _leaf(size: int, rule: str, seed: int) -> np.ndarray:
    """A flat leaf with ``_kept(size, rule)`` nonzeros at random places;
    magnitudes in [1, 2) so int8 quantises none of them to the same code
    by accident of range."""
    rng = np.random.default_rng(seed)
    x = np.zeros((size,), np.float32)
    where = rng.choice(size, _kept(size, rule), replace=False)
    sign = rng.choice([-1.0, 1.0], where.size)
    x[where] = sign * (1.0 + rng.random(where.size))
    return x


def _tree(leaves):
    """Leaves as a dict tree, with a 300 x 77 leaf kept 2-D."""
    tree = {}
    for n, (size, rule) in enumerate(leaves):
        x = _leaf(size, rule, seed=n)
        tree[f"l{n}"] = jnp.asarray(x.reshape(300, 77) if size == 300 * 77
                                    else x)
    return tree


# Every entry of the 40,000-element leaf is kept: past its k = 20,000 and
# past its 24,576-slot region, so tiles past the region must go unwritten,
# or they would land in the next leaf's region, which nothing fills.
_MIXED = [(3079, "over"), (7, "under"), (70000, "exact"), (40000, "all"),
          (1025, "none"), (300 * 77, "under")]

# (leaves, quantize, slab_rows, clients under jit(vmap) or None).  Each
# size meets each kept count across the two size cases.
_RULES = ["none", "under", "exact", "over"]
CASES = {
    "sizes-kept-0-under-exact-over": (list(zip(SIZES, _RULES)), False, None,
                                      None),
    "sizes-kept-over-0-under-exact": (list(zip(SIZES, _RULES[-1:]
                                               + _RULES[:-1])),
                                      False, 32, None),
    "tree-fill-resets-f32": (_MIXED, False, 32, None),
    "tree-fill-resets-int8": (_MIXED, True, 128, None),
    "codec-jit-vmap-clients": (_MIXED, True, None, 3),
}


def _encode(tree, quantize, slab, clients):
    if clients is None:
        return ops.topk_encode_pytree(
            tree, GAMMA, quantize=quantize, assume_masked=True,
            interpret=True, slab_rows=slab)
    # the round's position: the codec under jit(vmap) over a client axis,
    # each client a different scaling (and so different int8 codes)
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (1.0 + 0.25 * c) for c in range(clients)]),
        tree)
    codec = FusedSparseCodec(gamma=GAMMA, quantized=quantize, interpret=True)
    return jax.jit(jax.vmap(codec.encode))(stacked)


def _as_bytes(leaf):
    a = np.asarray(leaf)
    return a.dtype, a.shape, a.tobytes()


@pytest.mark.parametrize("case", sorted(CASES))
def test_compaction_is_byte_identical_to_scatter_reference(case,
                                                           monkeypatch):
    leaves, quantize, slab, clients = CASES[case]
    tree = _tree(leaves)
    got = _encode(tree, quantize, slab, clients)
    monkeypatch.setattr(
        seg, "segmented_compact",
        lambda vals, bitmap, spec, budgets, **_: ops.coo_compact_reference(
            vals, bitmap, spec, budgets))
    want = _encode(tree, quantize, slab, clients)

    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert _as_bytes(g) == _as_bytes(w)
    # every maskable leaf is a COO payload with its budget's slots
    for name, (size, _) in zip(sorted(tree), leaves):
        if size >= 256:
            k = max(1, round(GAMMA * size))
            assert got[name]["indices"].shape[-1] == k
            shape = np.asarray(got[name]["shape"]).reshape(-1, len(
                tree[name].shape))[0]
            assert tuple(shape) == tree[name].shape
