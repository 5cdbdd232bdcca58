"""Sparse payload encoding + byte accounting for masked uploads.

The paper states masked models are "compressed when uploaded" but does not fix
an encoding.  We implement the two standard ones and meter both:

* **bitmap**   — 1 bit/parameter membership + gamma*P dense values.
* **coordinate** — gamma*P (index, value) pairs, 4-byte int32 indices.

Bitmap wins whenever gamma > value_bits/ (index_bits) ≈ 1/32 for fp32+int32,
so the cost model picks the cheaper automatically (``encoding="auto"``).
This byte accounting feeds the §Roofline collective term for the technique
(DESIGN.md §3.2) and the transport-cost numbers in EXPERIMENTS.md.

Both encodings are REAL wire transforms, not just byte models:
``encode_sparse``/``decode_sparse`` back ``codecs.SparseCodec`` and
``encode_bitmap``/``decode_bitmap`` back ``codecs.BitmapCodec`` (DESIGN.md
§10 derives the crossover).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.packing import pack_bits, unpack_bits

PyTree = Any

__all__ = [
    "payload_bytes",
    "pytree_num_params",
    "pytree_payload_bytes",
    "encode_sparse",
    "sparse_parts",
    "decode_sparse",
    "encode_bitmap",
    "decode_bitmap",
    "quantize_int8",
    "dequantize_int8",
    "quantize_pytree",
    "dequantize_pytree",
    "CompressionStats",
]


@dataclasses.dataclass(frozen=True)
class CompressionStats:
    """``encoding`` is the single encoding used, or "mixed" when leaves chose
    differently (auto mode routinely mixes bitmap big leaves with dense small
    ones); ``encoding_bytes`` carries the exact per-encoding byte totals so
    mixed uploads are metered correctly."""

    dense_bytes: int
    sparse_bytes: int
    encoding: str
    encoding_bytes: Mapping[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def ratio(self) -> float:
        return self.sparse_bytes / max(self.dense_bytes, 1)


def payload_bytes(num_params: int, gamma: float, value_bytes: int = 4,
                  encoding: str = "auto") -> Tuple[int, str]:
    """Bytes to ship ``gamma * num_params`` kept values of one tensor."""
    kept = int(round(gamma * num_params))
    dense = num_params * value_bytes
    if gamma >= 1.0:
        return dense, "dense"
    bitmap = kept * value_bytes + (num_params + 7) // 8
    coord = kept * (value_bytes + 4)
    if encoding == "bitmap":
        return bitmap, "bitmap"
    if encoding == "coordinate":
        return coord, "coordinate"
    if encoding == "auto":
        return (bitmap, "bitmap") if bitmap <= coord else (coord, "coordinate")
    raise ValueError(f"unknown encoding {encoding!r}")


def pytree_num_params(tree: PyTree) -> int:
    return int(sum(np.prod(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(tree)))


def pytree_payload_bytes(tree: PyTree, gamma: float, min_leaf_size: int = 256,
                         value_bytes: int = 4,
                         encoding: str = "auto") -> CompressionStats:
    """Account a full model upload under per-leaf masking (small leaves dense).

    Byte totals are accumulated PER ENCODING across leaves — an upload that
    mixes bitmap-encoded big leaves with dense small leaves (the common case)
    reports the split in ``encoding_bytes`` rather than whatever the last
    leaf happened to pick.
    """
    dense = 0
    sparse = 0
    per_enc: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        n = int(np.prod(leaf.shape))
        dense += n * value_bytes
        if n < min_leaf_size or gamma >= 1.0:
            b, enc = n * value_bytes, "dense"
        else:
            b, enc = payload_bytes(n, gamma, value_bytes, encoding)
        sparse += b
        per_enc[enc] = per_enc.get(enc, 0) + b
    if len(per_enc) == 1:
        label = next(iter(per_enc))
    else:
        label = "mixed" if per_enc else "dense"
    return CompressionStats(dense, sparse, label, per_enc)


def encode_sparse(masked: jax.Array, k: int) -> Dict[str, jax.Array]:
    """Coordinate-encode a masked tensor: the k nonzero (index, value) pairs.

    Static-shape (k fixed); zero-padded if fewer nonzeros survived the
    threshold.  This is the per-leaf primitive behind
    ``repro.core.codecs.SparseCodec`` — the real client->server wire format;
    the pod path aggregates masked dense tensors and only *meters* these
    bytes.

    Slot selection is MAGNITUDE-ranked (stable, index tie-break): with at
    most k nonzeros the round-trip is bit-exact, and a tensor that
    overflows its slot budget (e.g. a tie plateau on the kernel top-k
    path) degrades gracefully by shedding its *smallest* values — i.e. it
    behaves as a slightly tighter top-k mask, never dropping dominant
    coordinates.  With error feedback on, the shed mass re-enters the
    residual (see ``make_federated_round``).
    """
    if k < 1:
        raise ValueError(f"encode_sparse needs k >= 1, got {k}")
    flat = masked.reshape(-1)
    if k > flat.size:
        raise ValueError(
            f"encode_sparse k={k} exceeds tensor size {flat.size}")
    nz = flat != 0
    # Zeros sort last (+inf key); nonzeros by descending magnitude.
    key = jnp.where(nz, -jnp.abs(flat.astype(jnp.float32)), jnp.inf)
    order = jnp.argsort(key)          # jnp.argsort is stable: index tie-break
    idx = order[:k].astype(jnp.int32)
    vals = flat[idx] * nz[idx].astype(flat.dtype)
    return {"indices": idx, "values": vals,
            "shape": np.asarray(masked.shape, np.int32)}


def _is_concrete(x: Any) -> bool:
    """True when value-level validation is possible (not an abstract
    tracer)."""
    return not isinstance(x, jax.core.Tracer)


def _as_array(x: Any, name: str):
    """Normalize a payload entry to something with shape/dtype, so the
    validators below raise the documented ``ValueError`` (not
    ``AttributeError``) on non-array garbage.  Tracers and arrays pass
    through; lists/scalars coerce via numpy."""
    if hasattr(x, "dtype") and hasattr(x, "shape"):
        return x
    try:
        arr = np.asarray(x)
    except Exception as e:
        raise ValueError(
            f"{name} is not array-like: {type(x).__name__}") from e
    if arr.dtype == object:
        raise ValueError(f"{name} is not array-like: {type(x).__name__}")
    return arr


def sparse_parts(payload: Dict[str, jax.Array]):
    """Validate a COO payload; returns ``(indices, values, shape)``.

    Malformed payloads fail loudly instead of silently scatter-adding
    garbage: missing keys, index/value length mismatch, non-integer
    indices, or (when the payload is concrete, i.e. not traced)
    out-of-range indices or non-finite values all raise ``ValueError``.
    Traced payloads cannot raise; the in-round quarantine gate
    (``repro.core.async_engine``) masks non-finite rows instead.
    """
    missing = {"indices", "values", "shape"} - set(payload)
    if missing:
        raise ValueError(f"sparse payload missing keys {sorted(missing)}")
    indices = _as_array(payload["indices"], "sparse indices")
    values = _as_array(payload["values"], "sparse values")
    if not jnp.issubdtype(indices.dtype, jnp.integer):
        raise ValueError(
            f"sparse indices must be integers, got {indices.dtype}")
    if indices.shape != values.shape or getattr(indices, "ndim", 1) != 1:
        raise ValueError(
            f"sparse indices/values must be matching 1-D arrays, got "
            f"{indices.shape} vs {values.shape}")
    shape = tuple(int(s) for s in payload["shape"])
    if any(s < 0 for s in shape):
        raise ValueError(f"sparse payload has negative shape {shape}")
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if indices.shape[0] > size:
        raise ValueError(
            f"sparse payload has {indices.shape[0]} slots for a tensor of "
            f"{size} elements")
    if _is_concrete(indices):
        idx = np.asarray(indices)
        if idx.size and (idx.min() < 0 or idx.max() >= size):
            raise ValueError(
                f"sparse indices out of range [0, {size}): "
                f"[{idx.min()}, {idx.max()}]")
    if _is_concrete(values):
        v = np.asarray(values)
        if (np.issubdtype(v.dtype, np.floating) and v.size
                and not np.isfinite(v).all()):
            raise ValueError(
                "sparse payload values contain non-finite entries")
    return indices, values, shape


def decode_sparse(payload: Dict[str, jax.Array]) -> jax.Array:
    """Decode a COO payload back to a dense tensor: every slot added into
    a zeroed tensor, after :func:`sparse_parts`' validation."""
    indices, values, shape = sparse_parts(payload)
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    out = jnp.zeros((size,), values.dtype)
    out = out.at[indices].add(values)
    return out.reshape(shape)


def encode_bitmap(masked: jax.Array, k: int) -> Dict[str, jax.Array]:
    """Bitmap-encode a masked tensor: 1 membership bit/element + k values.

    The wire format behind ``repro.core.codecs.BitmapCodec`` (DESIGN.md
    §10): ``bitmap`` packs the kept-entry membership mask LSB-first
    (byte ``b`` bit ``j`` describes element ``8 b + j``, trailing padding
    bits zero) and ``values`` carries the kept entries in INDEX order,
    zero-padded to the static k slots.  Bytes: ``ceil(n / 8) + k * vb`` vs
    COO's ``k * (4 + vb)`` — bitmap wins whenever the kept density
    ``k / n > 1 / 32``, independent of the value width vb.

    Slot selection mirrors :func:`encode_sparse`: magnitude-ranked with a
    stable index tie-break, so a tensor overflowing its budget sheds its
    smallest values and the round-trip is bit-exact whenever at most k
    nonzeros survived the mask.
    """
    if k < 1:
        raise ValueError(f"encode_bitmap needs k >= 1, got {k}")
    flat = masked.reshape(-1)
    n = flat.size
    if k > n:
        raise ValueError(f"encode_bitmap k={k} exceeds tensor size {n}")
    nz = flat != 0
    key = jnp.where(nz, -jnp.abs(flat.astype(jnp.float32)), jnp.inf)
    order = jnp.argsort(key)
    sel = order[:k]
    keep = jnp.zeros((n,), bool).at[sel].set(nz[sel])
    slot = jnp.cumsum(keep) - 1
    dest = jnp.where(keep, slot, k)          # non-kept -> trash slot k
    vals = jnp.zeros((k + 1,), flat.dtype).at[dest].set(
        jnp.where(keep, flat, jnp.zeros_like(flat)))[:k]
    return {"bitmap": pack_bits(keep), "values": vals,
            "shape": np.asarray(masked.shape, np.int32)}


def decode_bitmap(payload: Dict[str, jax.Array]) -> jax.Array:
    """Decode a bitmap payload back to a dense tensor.

    Mirrors :func:`decode_sparse`'s loud-failure contract: missing keys, a
    non-uint8 or wrongly-sized bitmap, non-1-D values, more value slots
    than elements, and — when the payload is concrete — stray bits in the
    trailing padding, a popcount exceeding the value slots, or non-finite
    values all raise ``ValueError``.  Traced payloads cannot raise; the
    in-round quarantine gate (``repro.core.async_engine``) masks
    non-finite rows instead, and an over-full traced bitmap clips to the
    first k set bits.
    """
    missing = {"bitmap", "values", "shape"} - set(payload)
    if missing:
        raise ValueError(f"bitmap payload missing keys {sorted(missing)}")
    bitmap = _as_array(payload["bitmap"], "bitmap payload bitmap")
    values = _as_array(payload["values"], "bitmap payload values")
    if bitmap.dtype != jnp.uint8:
        raise ValueError(
            f"bitmap payload bitmap must be uint8, got {bitmap.dtype}")
    if getattr(bitmap, "ndim", 1) != 1 or getattr(values, "ndim", 1) != 1:
        raise ValueError(
            f"bitmap payload bitmap/values must be 1-D, got shapes "
            f"{bitmap.shape} vs {values.shape}")
    shape = tuple(int(s) for s in payload["shape"])
    if any(s < 0 for s in shape):
        raise ValueError(f"bitmap payload has negative shape {shape}")
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    nb = (size + 7) // 8
    if bitmap.shape[0] != nb:
        raise ValueError(
            f"bitmap payload has {bitmap.shape[0]} bytes for a tensor of "
            f"{size} elements (expected {nb})")
    k = int(values.shape[0])
    if k < 1 or k > size:
        raise ValueError(
            f"bitmap payload has {k} value slots for a tensor of "
            f"{size} elements")
    bits = unpack_bits(bitmap, 8 * nb)
    if _is_concrete(bits):
        b = np.asarray(bits)
        if b[size:].any():
            raise ValueError(
                "bitmap payload has membership bits set in the trailing "
                "padding")
        if int(b[:size].sum()) > k:
            raise ValueError(
                f"bitmap payload popcount {int(b[:size].sum())} exceeds its "
                f"{k} value slots")
    if _is_concrete(values):
        v = np.asarray(values)
        if (np.issubdtype(v.dtype, np.floating) and v.size
                and not np.isfinite(v).all()):
            raise ValueError(
                "bitmap payload values contain non-finite entries")
    bits = bits[:size].astype(bool)
    slot = jnp.clip(jnp.cumsum(bits) - 1, 0, k - 1)
    out = jnp.where(bits, values[slot], jnp.zeros((), values.dtype))
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# int8 quantised uploads (beyond-paper; composes with selective masking)
# ---------------------------------------------------------------------------
def quantize_int8(x: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-tensor int8 quantisation of a (masked) delta.

    Composes with the paper's masking: zeros stay exactly zero (the scale
    maps 0 -> 0), so sparsity encoding is unaffected; the value payload
    drops from 4 to 1 byte per kept entry (bitmap encoding then costs
    gamma*P + P/8 bytes).
    """
    x = _as_array(x, "quantize_int8 input")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(f"quantize_int8 expects a float tensor, got {x.dtype}")
    # Explicit multiply-by-reciprocal, NOT ``/ 127.0``: XLA strength-reduces
    # constant divisions to reciprocal multiplies in some program shapes but
    # not others, and the ±1 ulp wobble in ``scale`` breaks the cross-engine
    # bit-exactness contract (tests/test_equivalence.py).  Writing the
    # multiply ourselves makes every compiled form — and the Pallas wire
    # kernel (repro/kernels/ops.py), which mirrors this constant — compute
    # the same bits.
    scale = jnp.max(jnp.abs(x)) * jnp.float32(1.0 / 127.0)
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def dequantize_int8(payload: Dict[str, jax.Array]) -> jax.Array:
    """Dequantize an int8 payload; malformed payloads raise ``ValueError``
    (missing keys, non-int8 values, non-scalar or non-finite scale)."""
    missing = {"q", "scale"} - set(payload)
    if missing:
        raise ValueError(f"int8 payload missing keys {sorted(missing)}")
    q = _as_array(payload["q"], "int8 payload q")
    scale = _as_array(payload["scale"], "int8 payload scale")
    if q.dtype != jnp.int8:
        raise ValueError(f"int8 payload q must be int8, got {q.dtype}")
    if getattr(scale, "ndim", 0) != 0:
        raise ValueError(
            f"int8 payload scale must be a scalar, got shape {scale.shape}")
    if _is_concrete(scale) and not np.isfinite(np.asarray(scale)):
        raise ValueError(
            f"int8 payload scale is non-finite: {np.asarray(scale)}")
    return q.astype(jnp.float32) * scale


def quantize_pytree(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(quantize_int8, tree)


def dequantize_pytree(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        dequantize_int8, tree,
        is_leaf=lambda t: isinstance(t, dict) and "q" in t)
