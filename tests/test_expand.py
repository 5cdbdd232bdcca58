"""The COO expand stage of the fused wire decode (DESIGN.md §10).

``FusedSparseCodec.decode`` writes each COO leaf's dense tensor with
``seg.segmented_expand``, the inverse of the encode's compaction, where the
oracle (``SparseCodec``'s ``compression.decode_sparse``) adds every slot
into a zeroed tensor.  Every case below encodes a tree with the fused
codec and decodes that one wire both ways: the dense trees must agree bit
for bit.

Leaves are built with a chosen number of nonzeros and encoded with
``assume_masked=True``, which ships every nonzero: so a case sets each
leaf's kept count against its budget k = round(0.5 * size) exactly.
"""

import functools

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codecs import ChainCodec, FusedSparseCodec, Int8Codec
from repro.core.codecs import SparseCodec
from repro.kernels import segmented as seg

GAMMA = 0.5
SIZES = [1023, 1024, 1025, 3 * 1024 + 7]
RULES = ["none", "under", "exact", "all"]


def _kept(size: int, rule: str) -> int:
    k = max(1, round(GAMMA * size))
    return {"none": 0, "under": k - max(1, k // 8), "ends": k // 3,
            "exact": k, "all": size}[rule]


def _leaf(size: int, rule: str, seed: int) -> np.ndarray:
    """A flat leaf with ``_kept(size, rule)`` nonzeros at random places
    (rule ``ends``: two of them at index 0 and n - 1); magnitudes in
    [1, 2) so int8 quantises none of them to zero."""
    rng = np.random.default_rng(seed)
    x = np.zeros((size,), np.float32)
    where = rng.choice(size, _kept(size, rule), replace=False)
    if rule == "ends":
        where[:2] = 0, size - 1
        where = np.unique(where)
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


# Seven elements are under min_leaf_size: that leaf ships dense.
_SMALL = [(7, "under"), (300 * 77, "under"), (1025, "exact")]

# (leaves, quantized, clients under jit(vmap) or None, slab rows or None
# for the backend's default).  Each size meets each kept count across the
# two size cases.  32 slab rows hold 4 output tiles, so "multi-slab" runs
# a (2, 3) grid whose last slab has two tiles past every leaf.
CASES = {
    "sizes-kept-none-under-exact-all-f32": (list(zip(SIZES, RULES)), False,
                                            None, None),
    "sizes-kept-all-none-under-exact-int8": (
        list(zip(SIZES, RULES[-1:] + RULES[:-1])), True, None, None),
    "ends-f32": ([(1023, "ends"), (3079, "ends")], False, None, None),
    "ends-int8": ([(1025, "ends"), (70000, "ends")], True, None, None),
    "tree-small-leaf-f32": (_SMALL, False, None, None),
    "tree-small-leaf-int8": (_SMALL, True, None, None),
    "vmap-4-clients-f32": (_SMALL + [(3079, "ends")], False, 4, None),
    "vmap-4-clients-int8": (_SMALL + [(3079, "ends")], True, 4, None),
    "multi-slab-vmap-2-clients-f32": ([(70000, "under"), (3079, "ends")],
                                      False, 2, 32),
}


def _oracle(quantized: bool):
    base = SparseCodec(gamma=GAMMA)
    return ChainCodec((base, Int8Codec())) if quantized else base


def _expand_launches(fn, *args):
    """The client-grid sizes of the ``fl_seg_expand`` kernel launches in
    the traced program of ``fn``."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "pallas_call"
                    and eqn.params["name"] == "fl_seg_expand"):
                grids.append(eqn.params["grid_mapping"].grid[0])
            for p in eqn.params.values():
                if isinstance(p, jex.core.ClosedJaxpr):
                    walk(p.jaxpr)
                elif isinstance(p, jex.core.Jaxpr):
                    walk(p)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def _as_bytes(leaf):
    a = np.asarray(leaf)
    return a.dtype, a.shape, a.tobytes()


@pytest.mark.parametrize("case", sorted(CASES))
def test_expand_is_bit_identical_to_decode_sparse(case, monkeypatch):
    leaves, quantized, clients, slab_rows = CASES[case]
    if slab_rows is not None:
        monkeypatch.setattr(seg, "segmented_expand", functools.partial(
            seg.segmented_expand, slab_rows=slab_rows))
    tree = _tree(leaves)
    codec = FusedSparseCodec(gamma=GAMMA, quantized=quantized,
                             interpret=True)
    if clients is None:
        # a concrete wire: the decode's validators run on it
        wire = codec.encode(tree)
        got, want = codec.decode(wire), _oracle(quantized).decode(wire)
    else:
        # the round's position: the codec under jit(vmap) over a client
        # axis, each client a different scaling
        tree = jax.tree.map(
            lambda x: jnp.stack([x * (1.0 + 0.25 * c)
                                 for c in range(clients)]), tree)
        got = jax.jit(jax.vmap(codec.roundtrip))(tree)
        want = jax.jit(jax.vmap(
            lambda t: _oracle(quantized).decode(codec.encode(t))))(tree)
        assert _expand_launches(jax.vmap(codec.roundtrip), tree) == [clients]

    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert _as_bytes(g) == _as_bytes(w)
    # the decode gives back what was kept: every nonzero of a leaf within
    # its budget (f32), each leaf at its own shape
    for name, (size, rule) in zip(sorted(tree), leaves):
        assert got[name].shape == tree[name].shape
        if not quantized and _kept(size, rule) <= round(GAMMA * size):
            assert _as_bytes(got[name]) == _as_bytes(tree[name])


# Concrete COO payloads out of the fused wire's layout; each decodes
# through the oracle, which adds every slot wherever it lies.
BAD = {
    "descending": ([9, 5, 12, 0], [1.0, 2.0, 3.0, 0.0]),
    "repeated": ([5, 5, 12, 0], [1.0, 2.0, 3.0, 0.0]),
    "live-after-pad": ([5, 9, 0, 12], [1.0, 2.0, 0.0, 3.0]),
    "valued-pad": ([5, 9, 12, 0], [1.0, 2.0, 3.0, 4.0]),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_expand_rejects_a_wire_out_of_its_layout(case):
    indices, values = BAD[case]
    wire = {"w": {"indices": np.asarray(indices, np.int32),
                  "values": np.asarray(values, np.float32),
                  "shape": np.asarray([300], np.int32)}}
    dense = SparseCodec(gamma=GAMMA).decode(wire)["w"]
    assert float(jnp.sum(dense)) == float(sum(values))
    with pytest.raises(ValueError, match="strictly ascending"):
        FusedSparseCodec(gamma=GAMMA, interpret=True).decode(wire)
