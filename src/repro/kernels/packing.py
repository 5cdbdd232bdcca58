"""Leaf packing for the segmented whole-pytree masking kernels (DESIGN.md §3.4).

The per-leaf kernel pipeline in ``ops.topk_mask`` pays O(L * (iters + 2)) HBM
sweeps and kernel launches for an L-leaf model, plus one ``pallas_call`` trace
per distinct leaf shape.  The segmented path instead packs every maskable leaf
into ONE padded ``(R, SEG_LANE)`` fp32 buffer with a static per-ROW segment-id
map, so the whole model is swept in a leaf-count-independent number of passes
(see ``repro.kernels.segmented``).

Layout
------
Each leaf is flattened, cast to fp32, zero-padded up to a whole number of
SEG_LANE-wide rows and concatenated.  A row therefore belongs to exactly ONE
leaf, and the (R, 1) int32 ``seg_ids`` array — a *static* numpy constant
derived purely from leaf shapes — tells the kernels which histogram / count /
tau row each data row contributes to.  Row granularity keeps worst-case
padding at SEG_LANE - 1 elements per leaf (vs. a whole kernel tile if the
map were per grid block), and the kernels turn the per-row ids into one-hot
matrices contracted with matmuls — no dynamic indexing anywhere.  Padding
zeros never survive masking because every selected threshold is > 0.

All metadata (offsets, shapes, dtypes, row counts) is static Python data, so
``pack_leaves`` / ``unpack_leaves`` are jit/scan/pjit-safe: under ``jax.jit``
the pack is a fused pad+concat+reshape and the unpack a set of static slices.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SEG_LANE",
    "LeafSpec",
    "PackSpec",
    "build_pack_spec",
    "pack_leaves",
    "unpack_leaves",
    "pack_bits",
    "unpack_bits",
]

# Lane width of the packed buffer; also the per-leaf padding granularity.
# A multiple of 128 for the VPU lane axis.
SEG_LANE = 1024


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static placement of one leaf inside the packed buffer."""

    shape: Tuple[int, ...]
    dtype: Any
    size: int
    offset: int      # element offset of the leaf's first entry
    num_rows: int    # SEG_LANE-wide rows this leaf occupies (size padded up)


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static description of a packed multi-leaf buffer.

    ``seg_ids`` maps row index -> segment (leaf) index; it is a numpy
    constant so it closes over traces without becoming a traced value.
    """

    leaves: Tuple[LeafSpec, ...]
    total_rows: int

    @property
    def num_segments(self) -> int:
        """Number of packed leaves (segments)."""
        return len(self.leaves)

    @property
    def rows(self) -> int:
        """Total SEG_LANE-wide rows in the packed buffer."""
        return self.total_rows

    def seg_ids(self) -> np.ndarray:
        """(rows, 1) int32 row -> segment map, as a numpy constant."""
        out = np.empty((self.total_rows, 1), np.int32)
        for s, leaf in enumerate(self.leaves):
            start = leaf.offset // SEG_LANE
            out[start:start + leaf.num_rows] = s
        return out

    def sizes(self) -> np.ndarray:
        """(num_segments,) int32 element counts per leaf."""
        return np.asarray([leaf.size for leaf in self.leaves], np.int32)


def build_pack_spec(leaves: Sequence[jax.Array]) -> PackSpec:
    """Derive the static packing layout from leaf shapes/dtypes only."""
    specs: List[LeafSpec] = []
    offset = 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        num_rows = max(1, -(-size // SEG_LANE))
        specs.append(LeafSpec(tuple(leaf.shape), leaf.dtype, size,
                              offset, num_rows))
        offset += num_rows * SEG_LANE
    return PackSpec(tuple(specs), offset // SEG_LANE)


def pack_leaves(leaves: Sequence[jax.Array],
                spec: PackSpec | None = None) -> Tuple[jax.Array, PackSpec]:
    """Pack ``leaves`` into one (rows, SEG_LANE) fp32 buffer.

    Returns ``(x2d, spec)``; pass a pre-built ``spec`` to skip re-derivation
    (it must match the leaves' shapes).
    """
    if spec is None:
        spec = build_pack_spec(leaves)
    # Write each leaf into a zeroed buffer at its static offset: one
    # allocation + one copy per leaf.  (A concatenate of per-leaf padded
    # flats costs ~9x more wall-clock on CPU and lowers worse on TPU.)
    buf = jnp.zeros((spec.rows * SEG_LANE,), jnp.float32)
    for leaf, ls in zip(leaves, spec.leaves):
        buf = jax.lax.dynamic_update_slice(
            buf, leaf.reshape(-1).astype(jnp.float32), (ls.offset,))
    return buf.reshape(spec.rows, SEG_LANE), spec


def unpack_leaves(x2d: jax.Array, spec: PackSpec) -> List[jax.Array]:
    """Invert ``pack_leaves``: static slices back to original shapes/dtypes."""
    flat = x2d.reshape(-1)
    out = []
    for ls in spec.leaves:
        leaf = jax.lax.slice_in_dim(flat, ls.offset, ls.offset + ls.size)
        out.append(leaf.reshape(ls.shape).astype(ls.dtype))
    return out


def pack_bits(bits: jax.Array) -> jax.Array:
    """(n,) 0/1 membership -> (ceil(n / 8),) uint8, LSB-first: byte ``b``
    bit ``j`` is element ``8 b + j``, trailing padding bits zero.  Summed
    from eight stride-8 slices: the ``(n / 8, 8)`` reshape form takes the
    TPU compiler minutes at embedding widths."""
    b = jnp.pad(bits.astype(jnp.int32), (0, (-bits.shape[0]) % 8))
    return sum(b[j::8] << j for j in range(8)).astype(jnp.uint8)


def unpack_bits(bitmap: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits`: the first ``n`` membership bits as
    int32 0/1, gathered per element (same compile-time reason)."""
    e = jnp.arange(n, dtype=jnp.int32)
    return (bitmap.astype(jnp.int32)[e >> 3] >> (e & 7)) & 1
