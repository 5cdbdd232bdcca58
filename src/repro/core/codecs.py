"""Wire codecs: real ``encode -> wire pytree -> decode`` upload transforms.

The paper says masked models are "compressed when uploaded" without fixing a
format.  Earlier revisions only *estimated* upload bytes
(``compression.pytree_payload_bytes``); this module is the real wire layer a
:class:`repro.core.strategy.FedStrategy` plugs in:

* ``IdentityCodec``     — dense pass-through (the baseline wire format).
* ``SparseCodec``       — per-leaf coordinate (COO) encoding of a masked
  delta: ``k = max(1, round(gamma * n))`` int32 index + value pairs per
  maskable leaf (leaves under ``min_leaf_size`` ship dense, mirroring
  ``MaskingConfig``).  Bit-exact round-trip whenever the tensor has at most
  k nonzeros — which the threshold masks guarantee (DESIGN.md §3.1).
* ``Int8Codec``         — symmetric per-tensor int8 quantisation of every
  float leaf (zeros stay exactly zero); 4 -> 1 value bytes.
* ``ChainCodec``        — composition, e.g. sparse COO then int8 on the
  surviving values (``Chain(Sparse, Int8)``); decode runs in reverse.
* ``BitmapCodec``       — per-leaf 1-bit/element membership bitmap + k
  values in index order: ``ceil(n/8) + k*vb`` bytes vs COO's ``k*(4+vb)``,
  cheaper whenever the kept density exceeds 1/32 (DESIGN.md §10).
* ``FusedSparseCodec``  — the kernel-backed wire path (DESIGN.md §10):
  ``encode`` runs ``repro.kernels.ops.topk_encode_pytree`` over the
  masked delta, so the COO/bitmap payload (optionally int8-quantised) is
  emitted by ONE fused Pallas sweep instead of the three re-reads the jnp
  codecs above cost.  Wire layout, bytes and decoded values are identical
  to the equivalent jnp codec (``SparseCodec`` / ``BitmapCodec`` /
  ``ChainCodec(..., Int8Codec())``) — the jnp codecs stay verbatim as the
  bit-exactness oracle.  ``decode`` of the COO wire is one Pallas expand
  launch, the inverse of the encode's compaction; the bitmap wire
  decodes through the oracle.

Every codec reports **exact** wire bytes: ``wire_bytes(tree)`` traces
``encode`` with ``jax.eval_shape`` (no FLOPs, no device buffers) and sums
the serialized nbytes of each wire leaf.  All wire shapes are static —
COO slot counts come from gamma and leaf shapes — so the byte count is
exact for every upload, not an estimate.

Encode/decode are jit/vmap-safe; ``roundtrip_stacked`` applies a codec to a
client-stacked upload pytree inside the federated round, so what the
aggregation consumes is exactly what survived the wire.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import (_is_concrete, decode_bitmap,
                                    decode_sparse, dequantize_int8,
                                    encode_bitmap, encode_sparse,
                                    quantize_int8, sparse_parts)

PyTree = Any

__all__ = [
    "UploadCodec",
    "IdentityCodec",
    "SparseCodec",
    "Int8Codec",
    "ChainCodec",
    "BitmapCodec",
    "FusedSparseCodec",
    "tree_wire_nbytes",
    "roundtrip_stacked",
    "with_axis0_slices",
]


def _reject_nonfinite(leaf: Any, codec_name: str) -> Any:
    """Decode-boundary validation shared by every codec: a *concrete*
    (host-side, untraced) float payload carrying NaN/Inf raises
    ``ValueError`` before it can reach aggregation or error-feedback
    state.  Traced payloads pass through — inside a jitted round the
    async engine's quarantine gate masks non-finite rows instead
    (``repro.core.async_engine``)."""
    if _is_concrete(leaf):
        arr = np.asarray(leaf)
        if (np.issubdtype(arr.dtype, np.floating) and arr.size
                and not np.isfinite(arr).all()):
            raise ValueError(
                f"{codec_name} decode: payload contains non-finite values")
    return leaf


def _leaf_nbytes(leaf: Any) -> int:
    """Serialized size of one wire leaf — works on concrete arrays and on
    the ``ShapeDtypeStruct`` avals ``jax.eval_shape`` returns."""
    return int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize


def tree_wire_nbytes(wire: PyTree) -> int:
    """Exact serialized bytes of a wire pytree: sum of leaf nbytes."""
    return int(sum(_leaf_nbytes(leaf)
                   for leaf in jax.tree_util.tree_leaves(wire)))


@dataclasses.dataclass(frozen=True)
class UploadCodec:
    """Base wire codec.  Subclasses implement ``encode``/``decode`` as pure
    jit-able pytree transforms with static wire shapes."""

    name = "identity"

    def encode(self, tree: PyTree) -> PyTree:
        """Upload pytree -> wire pytree (static shapes)."""
        raise NotImplementedError

    def decode(self, wire: PyTree) -> PyTree:
        """Wire pytree -> upload pytree (inverse of :meth:`encode`)."""
        raise NotImplementedError

    def roundtrip(self, tree: PyTree) -> PyTree:
        """What the server sees after the upload crosses the wire.  The
        two halves run under the ``fl.encode`` / ``fl.decode`` scopes, so
        a device trace names each op's side of the wire."""
        with jax.named_scope("fl.encode"):
            wire = self.encode(tree)
        with jax.named_scope("fl.decode"):
            return self.decode(wire)

    def wire_bytes(self, tree: PyTree) -> int:
        """EXACT bytes of ``encode(tree)`` — shape-only (eval_shape), so it
        never materializes the wire."""
        template = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
        return tree_wire_nbytes(jax.eval_shape(self.encode, template))


@dataclasses.dataclass(frozen=True)
class IdentityCodec(UploadCodec):
    """Dense pass-through: the wire is the pytree itself."""

    name = "identity"

    def encode(self, tree: PyTree) -> PyTree:
        """The wire IS the upload pytree."""
        return tree

    def decode(self, wire: PyTree) -> PyTree:
        """The upload IS the wire pytree — after the non-finite gate."""
        return jax.tree_util.tree_map(
            lambda leaf: _reject_nonfinite(leaf, "identity"), wire)

    def roundtrip(self, tree: PyTree) -> PyTree:
        """Free: dense pass-through loses nothing."""
        return tree


def _is_coo(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "indices" in leaf and "values" in leaf


def _is_q8(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "q" in leaf and "scale" in leaf


@dataclasses.dataclass(frozen=True)
class SparseCodec(UploadCodec):
    """Per-leaf COO wire format for masked uploads.

    Mirrors the masking config it rides with: leaves smaller than
    ``min_leaf_size`` were never masked, so they ship dense; every other
    leaf ships ``k = max(1, round(gamma * n))`` (index, value) slots —
    the static capacity the threshold masks fill to at most k nonzeros
    (DESIGN.md §3.1), zero-padded below that.  Round-trip is bit-exact
    under that contract (property-tested in tests/test_codecs.py).
    """

    gamma: float = 0.1
    min_leaf_size: int = 256
    # Slot budgeting convention.  False (default): one top-k budget per
    # whole leaf — matches ``core.masking.mask_pytree``.  True: ndim >= 2
    # leaves get ``shape[0] * max(1, round(gamma * slice_size))`` slots —
    # matches the pod path's per-first-axis-slice masks
    # (``launch.fedtrain._threshold_mask`` / the kernel route), which can
    # keep more than round(gamma * n) entries per leaf in total.
    axis0_slices: bool = False

    @property
    def name(self) -> str:  # type: ignore[override]
        """Wire-format label surfaced in ``FederatedServer.summary()``."""
        suffix = ", per-slice" if self.axis0_slices else ""
        return f"sparse(gamma={self.gamma}{suffix})"

    def _slots(self, size: int) -> int:
        return max(1, int(round(self.gamma * size)))

    def _leaf_slots(self, leaf) -> int:
        if self.axis0_slices and leaf.ndim >= 2:
            return leaf.shape[0] * self._slots(leaf.size // leaf.shape[0])
        return self._slots(leaf.size)

    def encode(self, tree: PyTree) -> PyTree:
        """COO-encode every maskable leaf (small leaves ship dense)."""
        def enc(leaf):
            if leaf.size < self.min_leaf_size or self.gamma >= 1.0:
                return leaf
            return encode_sparse(leaf, min(self._leaf_slots(leaf), leaf.size))

        return jax.tree_util.tree_map(enc, tree)

    def decode(self, wire: PyTree) -> PyTree:
        """Scatter every COO leaf back to dense; pass dense leaves (after
        the non-finite gate — COO values are checked in decode_sparse)."""
        return jax.tree_util.tree_map(
            lambda leaf: (decode_sparse(leaf) if _is_coo(leaf)
                          else _reject_nonfinite(leaf, self.name)),
            wire, is_leaf=_is_coo)


@dataclasses.dataclass(frozen=True)
class Int8Codec(UploadCodec):
    """Symmetric per-tensor int8 quantisation of every float leaf.

    Composable after :class:`SparseCodec`: int32 indices and shape metadata
    pass through untouched; only float value payloads quantise.  Zeros map
    to exactly zero, so sparsity structure survives; the dequantised error
    per entry is bounded by ``scale/2 = max|x| / 254`` (rounding half a
    step), property-tested in tests/test_codecs.py.
    """

    name = "int8"

    def encode(self, tree: PyTree) -> PyTree:
        """Quantise every float leaf to (int8 q, fp32 scale) pairs."""
        def enc(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                return quantize_int8(leaf)
            return leaf

        return jax.tree_util.tree_map(enc, tree)

    def decode(self, wire: PyTree) -> PyTree:
        """Dequantise every (q, scale) leaf back to float32; float
        pass-through leaves hit the non-finite gate (q8 scales are checked
        in dequantize_int8)."""
        return jax.tree_util.tree_map(
            lambda leaf: (dequantize_int8(leaf) if _is_q8(leaf)
                          else _reject_nonfinite(leaf, "int8")),
            wire, is_leaf=_is_q8)


def _check_ascending(indices: Any, values: Any) -> None:
    """The fused COO wire's layout, checked on a concrete payload: the
    live slots (slot 0, and every slot whose index is above 0) come
    first with strictly ascending indices, then (0, 0) pads."""
    if not (_is_concrete(indices) and _is_concrete(values)):
        return
    idx, val = np.asarray(indices), np.asarray(values)
    live = idx > 0
    live[:1] = True
    n = int(live.sum())
    if (not live[:n].all() or (np.diff(idx[:n]) <= 0).any()
            or (val[n:] != 0).any()):
        raise ValueError(
            "fused COO payload: live indices must be strictly ascending "
            "and followed only by (0, 0) pads")


def _is_bitmap(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "bitmap" in leaf and "values" in leaf


@dataclasses.dataclass(frozen=True)
class BitmapCodec(UploadCodec):
    """Per-leaf bitmap wire format for masked uploads (DESIGN.md §10).

    Same slot budgeting as :class:`SparseCodec` (leaves under
    ``min_leaf_size`` ship dense, others get ``k = max(1, round(gamma*n))``
    value slots), but membership ships as 1 bit/element instead of a 4-byte
    index per kept value: ``ceil(n/8) + k*vb`` wire bytes vs COO's
    ``k*(4+vb)``.  Bitmap is the cheaper wire whenever the kept density
    exceeds ``1/32`` (~3.1%) — independent of the value width, so the
    crossover survives int8 chaining.  Round-trip is bit-exact whenever at
    most k nonzeros survived the mask, like the COO codec.
    """

    gamma: float = 0.1
    min_leaf_size: int = 256

    @property
    def name(self) -> str:  # type: ignore[override]
        """Wire-format label surfaced in ``FederatedServer.summary()``."""
        return f"bitmap(gamma={self.gamma})"

    def _slots(self, size: int) -> int:
        return max(1, int(round(self.gamma * size)))

    def encode(self, tree: PyTree) -> PyTree:
        """Bitmap-encode every maskable leaf (small leaves ship dense)."""
        def enc(leaf):
            if leaf.size < self.min_leaf_size or self.gamma >= 1.0:
                return leaf
            return encode_bitmap(leaf, min(self._slots(leaf.size), leaf.size))

        return jax.tree_util.tree_map(enc, tree)

    def decode(self, wire: PyTree) -> PyTree:
        """Expand every bitmap leaf back to dense; pass dense leaves (after
        the non-finite gate — bitmap payloads are validated in
        decode_bitmap)."""
        return jax.tree_util.tree_map(
            lambda leaf: (decode_bitmap(leaf) if _is_bitmap(leaf)
                          else _reject_nonfinite(leaf, self.name)),
            wire, is_leaf=_is_bitmap)


@dataclasses.dataclass(frozen=True)
class FusedSparseCodec(UploadCodec):
    """Kernel-backed wire path: mask -> pack -> quantise in one HBM sweep.

    ``encode`` routes the (already-masked) upload pytree through
    ``repro.kernels.ops.topk_encode_pytree(assume_masked=True)`` — the
    fused segmented Pallas sweep emits the COO (``wire="coo"``) or bitmap
    (``wire="bitmap"``) payload, int8-quantised in the same pass when
    ``quantized`` — instead of re-reading the masked fp32 pytree three
    more times like the jnp codec chain (DESIGN.md §10,
    ``ops.wirepath_sweep_count``).

    The wire is structurally and byte-identical to the equivalent jnp
    codec — ``SparseCodec`` / ``BitmapCodec``, chained with
    :class:`Int8Codec` when ``quantized``.  Decoded values are bit-exact
    vs the oracle whenever each leaf's nonzero count fits its slot budget
    (threshold masks guarantee this off tie plateaus); on an overflowing
    plateau the fused path sheds by highest index where the oracle sheds
    smallest magnitude.

    ``decode`` of the COO wire is the inverse of the encode's compaction:
    one ``seg.segmented_expand`` launch writes every COO leaf's dense
    tensor, bit-identical to the oracle's ``decode_sparse`` (int8 values
    are dequantised first by the oracle's own ``dequantize_int8``).  It
    needs the layout the compaction writes: each leaf's live slots (slot
    0, and every slot whose index is above 0) first, with indices
    strictly ascending, then (0, 0) pads.  Inside a round the wire comes
    from the same ``roundtrip``'s encode, so that holds by construction;
    a concrete wire is checked, and one out of that layout raises
    ``ValueError``, as do the oracle's malformed-payload checks.  The
    bitmap wire decodes through the oracle.

    ``interpret=None`` picks by backend: compiled on a TPU, the Pallas
    interpreter elsewhere (the CPU tests).
    """

    gamma: float = 0.1
    min_leaf_size: int = 256
    quantized: bool = False
    wire: str = "coo"           # coo | bitmap
    interpret: bool | None = None

    def __post_init__(self):
        if self.wire not in ("coo", "bitmap"):
            raise ValueError(f"unknown wire format {self.wire!r}")

    @property
    def name(self) -> str:  # type: ignore[override]
        """Wire-format label surfaced in ``FederatedServer.summary()``."""
        kind = "bitmap" if self.wire == "bitmap" else "sparse"
        suffix = "+int8" if self.quantized else ""
        return f"fused-{kind}(gamma={self.gamma}){suffix}"

    def _oracle(self) -> UploadCodec:
        """The jnp codec whose wire this codec reproduces byte-for-byte."""
        base: UploadCodec = (
            BitmapCodec(gamma=self.gamma, min_leaf_size=self.min_leaf_size)
            if self.wire == "bitmap"
            else SparseCodec(gamma=self.gamma,
                             min_leaf_size=self.min_leaf_size))
        if self.quantized:
            return ChainCodec((base, Int8Codec()))
        return base

    def encode(self, tree: PyTree) -> PyTree:
        """One fused kernel sweep from masked delta to wire payload."""
        from repro.kernels import ops

        wire = ops.topk_encode_pytree(
            tree, self.gamma, min_leaf_size=self.min_leaf_size,
            quantize=self.quantized, wire=self.wire, assume_masked=True,
            interpret=self.interpret)
        if not self.quantized:
            return wire

        # The kernel only touches maskable leaves; quantise the small dense
        # float pass-through leaves here so the wire is byte-identical to
        # the ChainCodec oracle (whose Int8 stage quantises every float
        # leaf).
        def payload(leaf):
            return _is_coo(leaf) or _is_bitmap(leaf)

        def small(leaf):
            if not payload(leaf) and jnp.issubdtype(leaf.dtype, jnp.floating):
                return quantize_int8(leaf)
            return leaf

        return jax.tree_util.tree_map(small, wire, is_leaf=payload)

    def decode(self, wire: PyTree) -> PyTree:
        """Expand every COO leaf in one kernel launch (bitmap wire: the
        jnp oracle codec); dense leaves pass the non-finite gate."""
        from repro.kernels import ops
        from repro.kernels import packing as pk
        from repro.kernels import segmented as seg

        if self.wire == "bitmap":
            return self._oracle().decode(wire)
        if self.quantized:
            wire = Int8Codec().decode(wire)
        leaves, treedef = jax.tree_util.tree_flatten(wire, is_leaf=_is_coo)
        at = [i for i, leaf in enumerate(leaves) if _is_coo(leaf)]
        coo, shapes = [], []
        for i in at:
            indices, values, shape = sparse_parts(leaves[i])
            _check_ascending(indices, values)
            coo.append((indices, values))
            shapes.append(jax.ShapeDtypeStruct(shape, values.dtype))
        leaves = [leaf if _is_coo(leaf) else _reject_nonfinite(leaf, self.name)
                  for leaf in leaves]
        if at:
            dense = seg.segmented_expand(
                coo, pk.build_pack_spec(shapes),
                interpret=ops._auto_interpret(self.interpret))
            for i, leaf in zip(at, dense):
                leaves[i] = leaf
        return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass(frozen=True)
class ChainCodec(UploadCodec):
    """Left-to-right composition: ``encode`` folds forward through
    ``stages``, ``decode`` unwinds in reverse — e.g.
    ``ChainCodec((SparseCodec(g), Int8Codec()))`` ships int8-quantised COO
    values."""

    stages: Tuple[UploadCodec, ...] = ()

    def __post_init__(self):
        if not self.stages:
            raise ValueError("ChainCodec needs at least one stage")

    @property
    def name(self) -> str:  # type: ignore[override]
        """Stage names joined with "+" (e.g. ``sparse(gamma=0.5)+int8``)."""
        return "+".join(s.name for s in self.stages)

    def encode(self, tree: PyTree) -> PyTree:
        """Fold every stage's encode left-to-right."""
        for stage in self.stages:
            tree = stage.encode(tree)
        return tree

    def decode(self, wire: PyTree) -> PyTree:
        """Unwind every stage's decode in reverse order."""
        for stage in reversed(self.stages):
            wire = stage.decode(wire)
        return wire


def with_axis0_slices(codec: UploadCodec) -> UploadCodec:
    """Re-budget every SparseCodec stage to the pod path's
    per-first-axis-slice masking granularity (see
    ``SparseCodec.axis0_slices``); other codecs — including the
    whole-leaf-budgeted :class:`BitmapCodec` / :class:`FusedSparseCodec`,
    which are simulation-engine wires — pass through unchanged."""
    if isinstance(codec, SparseCodec):
        return dataclasses.replace(codec, axis0_slices=True)
    if isinstance(codec, ChainCodec):
        return ChainCodec(tuple(with_axis0_slices(s) for s in codec.stages))
    return codec


def roundtrip_stacked(codec: UploadCodec | None, stacked: PyTree) -> PyTree:
    """Round-trip a client-stacked upload pytree (leading client axis per
    leaf) through ``codec``, restoring each leaf's original dtype (int8
    dequantisation comes back f32).  ``None`` / identity are free."""
    if codec is None or isinstance(codec, IdentityCodec):
        return stacked
    wired = jax.vmap(codec.roundtrip)(stacked)
    with jax.named_scope("fl.decode"):
        return jax.tree_util.tree_map(
            lambda w, ref: w.astype(ref.dtype), wired, stacked)
