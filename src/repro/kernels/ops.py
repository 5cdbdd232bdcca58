"""Jit'd public wrappers around the Pallas masking kernels.

``topk_mask(x, gamma)`` keeps ~k = round(gamma * x.size) largest-|x| entries:
  1 histogram sweep + ``refine_iters`` count sweeps + 1 apply sweep
(= iters + 2 total; the histogram suffix-sums seed the bracket counts so the
final tau choice needs no extra sweep), vs the 24+ full bisection sweeps of
the pure-jnp path (see EXPERIMENTS.md §Perf for the sweep-count accounting).

``topk_mask_pytree(tree, gamma)`` masks EVERY maskable leaf of a delta pytree
in a leaf-count-independent number of sweeps (DESIGN.md §3.4):
  1 segmented histogram + ``refine_sweeps`` multi-candidate count sweeps
  + 1 fused count/apply sweep  (= 4 for the default config),
replacing the per-leaf Python loop of O(L * (iters + 2)) sweeps and its
per-shape ``pallas_call`` retraces.

``interpret=None`` picks by backend: on a TPU the kernels compile through
Mosaic (``tests/test_tpu_compile.py`` compiles them for a described v5e at
real widths; ``chip_smoke.py`` checks the compiled round holds them as
``tpu_custom_call``s); on any other backend they run in the Pallas
interpreter, which is how the CPU tests run them.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import packing as pk
from repro.kernels import segmented as seg
from repro.kernels import topk_mask as tk
from repro.kernels.ref import EXPO_MIN

PyTree = Any

__all__ = ["topk_mask", "topk_mask_pytree", "topk_encode_pytree",
           "coo_compact_reference", "pytree_sweep_count",
           "wirepath_sweep_count",
           "wirepath_bytes_moved", "masked_count"]


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to_blocks(flat: jax.Array) -> jax.Array:
    n = flat.shape[0]
    block = tk.BLOCK_ROWS * tk.LANE
    padded = ((n + block - 1) // block) * block
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // tk.LANE, tk.LANE)


@functools.partial(jax.jit, static_argnames=("gamma", "iters", "interpret"))
def topk_mask(x: jax.Array, gamma: float, iters: int = 8,
              interpret: bool | None = None) -> jax.Array:
    """Threshold-select the ~gamma fraction of largest-|x| entries of ``x``.

    Padding zeros never survive (the selected threshold is > 0), so arbitrary
    shapes are supported by flatten/pad/reshape.
    """
    interpret = _auto_interpret(interpret)
    n = x.size
    k = jnp.asarray(max(1, int(round(gamma * n))), jnp.int32)
    orig_dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    x2d = _pad_to_blocks(flat)

    hist = tk.exponent_histogram(x2d, interpret=interpret)
    tau_lo, tau_hi, _, cnt_hi = tk.select_threshold_counts(hist, k)

    def refine(_, carry):
        lo, hi, cnt_hi = carry
        mid = 0.5 * (lo + hi)
        cnt = tk.count_ge(x2d, mid, interpret=interpret)
        raise_lo = cnt > k
        lo = jnp.where(raise_lo, mid, lo)
        hi = jnp.where(raise_lo, hi, mid)
        cnt_hi = jnp.where(raise_lo, cnt_hi, cnt)  # hi moved -> its count is cnt
        return lo, hi, cnt_hi

    tau_lo, tau_hi, cnt_hi = jax.lax.fori_loop(
        0, iters, refine, (tau_lo, tau_hi, cnt_hi))
    # hi is the conservative endpoint: count(mag >= hi) <= k <= count(>= lo).
    # Use lo if hi would under-select badly (ties); cnt_hi was threaded
    # through the refine loop (seeded from the histogram suffix sums), so no
    # fresh counting sweep is needed here.
    tau = jnp.where(cnt_hi >= 1, tau_hi, tau_lo)

    out2d = tk.apply_threshold(x2d, tau, interpret=interpret)
    return out2d.reshape(-1)[:n].reshape(x.shape).astype(orig_dtype)


DEFAULT_REFINE_SWEEPS = 2
DEFAULT_CANDIDATES = 16


def pytree_sweep_count(num_leaves: int, *, segmented: bool = True,
                       iters: int = 8,
                       refine_sweeps: int = DEFAULT_REFINE_SWEEPS) -> int:
    """HBM sweeps to selectively mask an L-leaf pytree (analytic accounting).

    Per-leaf pipeline: every leaf pays 1 histogram + ``iters`` counts + 1
    apply.  Segmented: 1 histogram + ``refine_sweeps`` multi-candidate counts
    + 1 fused count/apply, independent of L.
    """
    if segmented:
        return 1 + refine_sweeps + 1
    return num_leaves * (iters + 2)


@functools.partial(jax.jit, static_argnames=(
    "gamma", "min_leaf_size", "refine_sweeps", "candidates", "interpret",
    "slab_rows"))
def topk_mask_pytree(tree: PyTree, gamma: float, *,
                     min_leaf_size: int = 256,
                     refine_sweeps: int = DEFAULT_REFINE_SWEEPS,
                     candidates: int = DEFAULT_CANDIDATES,
                     interpret: bool | None = None,
                     slab_rows: int | None = None) -> PyTree:
    """Whole-model selective masking in ~``refine_sweeps + 2`` HBM sweeps.

    Packs every leaf with >= ``min_leaf_size`` elements into one padded
    (R, LANE) buffer (kernels/packing.py) and runs the segmented kernels
    (kernels/segmented.py): one histogram sweep brackets every leaf's k-th
    magnitude to an octave, each refine sweep evaluates ``candidates``
    thresholds per leaf (shrinking the bracket (candidates+1)-fold), and one
    fused sweep applies the final per-leaf taus.  Leaves below
    ``min_leaf_size`` pass through dense, mirroring ``mask_pytree``.

    All packing metadata is static (shapes/dtypes only) — the function is
    jit/scan/pjit-safe and traces ONE pallas_call per kernel regardless of
    how many distinct leaf shapes the model has.

    Accuracy: per leaf, the kept count is <= k and misses at most the
    entries whose magnitude falls inside the final bracket around the k-th
    magnitude: the histogram brackets it to a 16x range, the geometric first
    sweep narrows that to ratio 16^(1/(candidates+1)), and each further
    linear sweep divides the width by candidates+1 — ~1% of tau for the
    defaults (C=16, 2 sweeps).  Property-tested against the sort oracle in
    tests/test_masking.py; magnitudes separated by more than that relative
    tolerance mask exactly.

    Tie caveat (shared with the per-leaf ``topk_mask`` pipeline): threshold
    selection cannot split entries of EQUAL magnitude, so when the bracket
    converges onto a tie plateau at the k-th magnitude, all tied entries are
    kept (the sort oracle instead drops surplus ties by index).  The <= k
    bound therefore holds only when the k-th and (k+1)-th magnitudes differ
    by more than the bracket resolution; degenerate inputs (e.g. a constant
    leaf) keep every tied entry.
    """
    interpret = _auto_interpret(interpret)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    mask_idx = [i for i, leaf in enumerate(leaves)
                if leaf.size >= min_leaf_size]
    if gamma >= 1.0 or not mask_idx:
        return tree

    sel = [leaves[i] for i in mask_idx]
    x2d, spec = pk.pack_leaves(sel)
    x2d, seg_ids = seg.pad_rows(x2d, jnp.asarray(spec.seg_ids()),
                                interpret=interpret, slab_rows=slab_rows)
    k = jnp.asarray([max(1, int(round(gamma * ls.size)))
                     for ls in spec.leaves], jnp.int32)

    hist = seg.segmented_histogram(x2d, seg_ids, spec.num_segments,
                                   interpret=interpret, slab_rows=slab_rows)
    lo, hi, cnt_lo, cnt_hi = seg.select_thresholds(hist, k)
    for sweep in range(refine_sweeps):
        # Sweep 0 subdivides the histogram's 16x bracket geometrically;
        # later sweeps refine the now-narrow bracket linearly.
        cand = seg.candidate_taus(lo, hi, candidates, geometric=(sweep == 0))
        counts = seg.segmented_count(x2d, seg_ids, cand, interpret=interpret,
                                     slab_rows=slab_rows)
        lo, hi, cnt_lo, cnt_hi = seg.shrink_brackets(
            lo, hi, cnt_lo, cnt_hi, cand, counts, k)

    # Conservative endpoint per segment; fall back to lo when hi would keep
    # nothing (counts were threaded through the refine — no extra sweep).
    tau = jnp.where(cnt_hi >= 1, hi, lo)
    out2d, _kept = seg.segmented_apply(x2d, seg_ids, tau, interpret=interpret,
                                       slab_rows=slab_rows)

    masked = pk.unpack_leaves(out2d[:spec.rows], spec)
    for i, m in zip(mask_idx, masked):
        leaves[i] = m
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --------------------------------------------------------------------------
# Fused wire path: delta pytree -> COO / bitmap wire payload (DESIGN.md §10).
# --------------------------------------------------------------------------
# "Keep everything nonzero" threshold for the assume-masked path: one bin
# below the histogram ladder's smallest edge, matching the underfull branch
# of seg.select_thresholds — magnitudes below 2^(EXPO_MIN-1) (~6e-30 for the
# default EXPO_MIN = -96) are treated as zero on the wire, the same floor
# the masking subsystem already applies.
_WIRE_FLOOR_TAU = float(2.0 ** (EXPO_MIN - 1))


def _leaf_slots(flat_bm, ls, k):
    """One packed leaf's keep-bits, capped at its k-slot budget, and each
    entry's destination slot.

    Slots follow index order via a cumulative sum of the LSB-first bits at
    the leaf's offset; overflow beyond the k slots is shed by highest index
    (the jnp oracle sheds smallest magnitude instead, an observable
    difference only on tie plateaus that overflow the budget) and sent to
    trash slot k.  Returns ``(live, dest)``.
    """
    byte0 = ls.offset // 8                       # offset is a SEG_LANE multiple
    bb = jax.lax.slice_in_dim(flat_bm, byte0, byte0 + (ls.size + 7) // 8)
    bits = pk.unpack_bits(bb, ls.size).astype(bool)
    slot = jnp.cumsum(bits) - 1
    live = bits & (slot < k)
    return live, jnp.where(live, slot, k)


def _scatter_slots(x, live, dest, k):
    """Scatter ``x``'s live entries into k static slots, zeros elsewhere.
    int8 scatters as int32: the TPU compiler takes ~5x longer on a sub-word
    scatter at embedding widths."""
    wide = jnp.int32 if x.dtype == jnp.int8 else x.dtype
    buf = jnp.zeros((k + 1,), wide).at[dest].set(
        jnp.where(live, x, jnp.zeros_like(x)).astype(wide))
    return buf[:k].astype(x.dtype)


def coo_compact_reference(vals, bitmap, spec, budgets):
    """Cumsum + scatter COO compaction: the reference that
    ``seg.segmented_compact`` (same arguments, same result) matches byte for
    byte.  Per segment ``(indices, values)``, (k,) each."""
    flat_v, flat_bm = vals.reshape(-1), bitmap.reshape(-1)
    out = []
    for ls, k in zip(spec.leaves, budgets):
        live, dest = _leaf_slots(flat_bm, ls, k)
        v = jax.lax.slice_in_dim(flat_v, ls.offset, ls.offset + ls.size)
        index = jnp.arange(ls.size, dtype=jnp.int32)
        out.append((_scatter_slots(index, live, dest, k),
                    _scatter_slots(v, live, dest, k)))
    return out


def topk_encode_pytree(tree: PyTree, gamma: float, *,
                       min_leaf_size: int = 256,
                       refine_sweeps: int = DEFAULT_REFINE_SWEEPS,
                       candidates: int = DEFAULT_CANDIDATES,
                       quantize: bool = False,
                       wire: str = "coo",
                       assume_masked: bool = False,
                       interpret: bool | None = None,
                       slab_rows: int | None = None) -> PyTree:
    """Delta pytree -> upload wire payload in one fused kernel pipeline.

    The wire-path successor of :func:`topk_mask_pytree` (DESIGN.md §10):
    instead of materialising a masked dense pytree for ``core.codecs`` to
    re-read three more times, the final segmented sweep
    (``seg.segmented_encode``) emits int8-quantised values, a 1-bit/element
    keep-bitmap and kept counts directly, and the compaction into static
    ``k = max(1, round(gamma * size))``-slot payloads reads only those
    narrow outputs (for COO, one ``seg.segmented_compact`` kernel over every
    leaf).  HBM cost: ``wirepath_sweep_count`` / ``wirepath_bytes_moved``.

    Per maskable leaf (``size >= min_leaf_size``) the returned pytree holds

    * ``wire="coo"``    — ``{"indices", "values", "shape"}``, decoded by
      ``core.compression.decode_sparse``;
    * ``wire="bitmap"`` — ``{"bitmap", "values", "shape"}`` (LSB-first
      membership bits), decoded by ``core.compression.decode_bitmap``;

    with ``values = {"q": int8, "scale": f32}`` when ``quantize`` (the scale
    is ``max|leaf| / 127``, computed by the stats sweep — identical to
    ``compression.quantize_int8`` because top-k keeps the max-magnitude
    entry).  Smaller leaves pass through dense and UNQUANTISED — the codec
    layer (``core.codecs.FusedSparseCodec``) owns small-leaf quantisation so
    wire bytes match the jnp ``ChainCodec`` oracle exactly.

    ``assume_masked=True`` skips threshold selection (the input is already a
    masked delta, e.g. inside the codec layer): every entry with magnitude
    above the masking subsystem's floor (2^(EXPO_MIN-1)) is shipped, so the
    pipeline costs 1 sweep (2 with ``quantize``, for the scale) instead of
    ``refine_sweeps + 2``.  Decoded payloads are then bit-exact vs the jnp
    ``SparseCodec``/``Int8Codec`` oracle whenever each leaf's nonzero count
    fits its slot budget — which threshold masks guarantee off tie plateaus
    (property-tested in tests/test_wirepath.py).

    Note the packed buffer is fp32 (``kernels.packing``): non-float leaves
    are shipped through the same f32 cast the masking path applies, and
    ``quantize`` treats every maskable leaf as float.

    Deliberately NOT ``@jax.jit``-wrapped: each payload's ``"shape"`` entry
    is a static numpy constant (like ``PackSpec``), which a whole-function
    jit would turn into a traced array and break the decoders' static
    shape handling.  Jit the enclosing computation instead — the round
    engines do (``codecs.roundtrip_stacked`` under the round's jit/vmap).
    """
    if wire not in ("coo", "bitmap"):
        raise ValueError(f"unknown wire format {wire!r}")
    interpret = _auto_interpret(interpret)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    mask_idx = [i for i, leaf in enumerate(leaves)
                if leaf.size >= min_leaf_size]
    if gamma >= 1.0 or not mask_idx:
        return tree

    sel = [leaves[i] for i in mask_idx]
    x2d, spec = pk.pack_leaves(sel)
    x2d, seg_ids = seg.pad_rows(x2d, jnp.asarray(spec.seg_ids()),
                                interpret=interpret, slab_rows=slab_rows)
    S = spec.num_segments

    scales = None
    if assume_masked:
        tau = jnp.full((S,), _WIRE_FLOOR_TAU, jnp.float32)
        if quantize:
            _, amax = seg.segmented_stats(x2d, seg_ids, S,
                                          interpret=interpret,
                                          slab_rows=slab_rows)
            scales = jnp.maximum(amax[:, 0] * jnp.float32(1.0 / 127.0),
                                 1e-12)
    else:
        k = jnp.asarray([max(1, int(round(gamma * ls.size)))
                         for ls in spec.leaves], jnp.int32)
        hist, amax = seg.segmented_stats(x2d, seg_ids, S, interpret=interpret,
                                         slab_rows=slab_rows)
        lo, hi, cnt_lo, cnt_hi = seg.select_thresholds(hist, k)
        for sweep in range(refine_sweeps):
            cand = seg.candidate_taus(lo, hi, candidates,
                                      geometric=(sweep == 0))
            counts = seg.segmented_count(x2d, seg_ids, cand,
                                         interpret=interpret,
                                         slab_rows=slab_rows)
            lo, hi, cnt_lo, cnt_hi = seg.shrink_brackets(
                lo, hi, cnt_lo, cnt_hi, cand, counts, k)
        tau = jnp.where(cnt_hi >= 1, hi, lo)
        if quantize:
            scales = jnp.maximum(amax[:, 0] * jnp.float32(1.0 / 127.0),
                                 1e-12)

    out2d, bm2d, _kept = seg.segmented_encode(
        x2d, seg_ids, tau, scales, interpret=interpret, slab_rows=slab_rows)
    budgets = [min(max(1, int(round(gamma * ls.size))), ls.size)
               for ls in spec.leaves]
    if wire == "coo":
        coo = seg.segmented_compact(out2d, bm2d, spec, budgets,
                                    interpret=interpret, slab_rows=slab_rows)
    flat_vals, flat_bm = out2d.reshape(-1), bm2d.reshape(-1)
    for s, (i, ls, k) in enumerate(zip(mask_idx, spec.leaves, budgets)):
        if wire == "coo":
            payload = {"indices": coo[s][0]}
            values = coo[s][1]
        else:
            # bitmap wire: repack the budget-capped bits so the popcount
            # can never exceed the value slots (byte-identical to
            # compression.encode_bitmap).
            live, dest = _leaf_slots(flat_bm, ls, k)
            v = jax.lax.slice_in_dim(flat_vals, ls.offset,
                                     ls.offset + ls.size)
            payload = {"bitmap": pk.pack_bits(live)}
            values = _scatter_slots(v, live, dest, k)
        if scales is not None:
            payload["values"] = {"q": values,
                                 "scale": scales[s].astype(jnp.float32)}
        else:
            payload["values"] = values.astype(ls.dtype)
        payload["shape"] = np.asarray(ls.shape, np.int32)
        leaves[i] = payload
    return jax.tree_util.tree_unflatten(treedef, leaves)


def wirepath_sweep_count(*, fused: bool,
                         refine_sweeps: int = DEFAULT_REFINE_SWEEPS,
                         assume_masked: bool = False,
                         quantize: bool = True) -> int:
    """Full-width HBM passes over an n-param delta to build ONE upload's
    wire payload (DESIGN.md §10).

    A "sweep" is a read or write of the full fp32 packed buffer; the fused
    path's narrow int8/bitmap writes and its k-slot compaction reads
    (1.125 bytes/param vs 4) are sub-width and accounted in
    :func:`wirepath_bytes_moved`, not here.

    * fused      — 1 stats (histogram + absmax) + ``refine_sweeps`` counts
      + 1 fused encode; with ``assume_masked`` the selection sweeps vanish
      (1 encode, + 1 absmax sweep when ``quantize``).
    * jnp oracle — the same masking front half PLUS a dense fp32 write
      (apply), then the codec re-reads the masked tree three more times
      (sort-key build, argsort, gather) to build the COO payload.
    """
    if fused:
        if assume_masked:
            return 2 if quantize else 1
        return 1 + refine_sweeps + 1
    select = 0 if assume_masked else 1 + refine_sweeps
    return select + 2 + 3


def wirepath_bytes_moved(n_params: int, gamma: float, *, fused: bool,
                         quantize: bool = True, wire: str = "coo",
                         refine_sweeps: int = DEFAULT_REFINE_SWEEPS,
                         assume_masked: bool = False) -> dict:
    """Analytic HBM bytes (reads + writes) to wire-encode one n-param delta.

    The roofline companion of :func:`wirepath_sweep_count` — every term is a
    byte count over the packed fp32 buffer (4 bytes/param) or the fused
    sweep's narrow outputs (1 byte/param int8, 1 bit/param bitmap), so
    ``total / hbm_bandwidth`` is the wire path's HBM-bound time floor
    (benchmarks/roofline.py).  Returns a dict with ``reads``, ``writes``,
    ``total``, ``payload_bytes`` and the per-stage ``breakdown``.
    """
    n = int(n_params)
    dense = 4 * n
    k = min(max(1, int(round(gamma * n))), n)
    vb = 1 if quantize else 4
    payload = (k * (4 + vb)) if wire == "coo" else (k * vb + (n + 7) // 8)
    if quantize:
        payload += 4                                   # fp32 scale
    breakdown = {}
    if not assume_masked:
        breakdown["select_reads"] = (1 + refine_sweeps) * dense
    elif fused and quantize:
        breakdown["select_reads"] = dense              # absmax-only sweep
    if fused:
        narrow = (n if quantize else dense) + (n + 7) // 8
        breakdown["encode_read"] = dense
        breakdown["encode_writes"] = narrow            # int8/fp32 + bitmap
        breakdown["compact_reads"] = narrow            # never fp32 again
        breakdown["payload_writes"] = payload
    else:
        breakdown["apply_read"] = dense
        breakdown["apply_write"] = dense               # masked fp32 pytree
        breakdown["codec_rereads"] = 3 * dense         # key, argsort, gather
        breakdown["payload_writes"] = payload
    reads = (breakdown.get("select_reads", 0)
             + breakdown.get("encode_read", 0)
             + breakdown.get("compact_reads", 0)
             + breakdown.get("apply_read", 0)
             + breakdown.get("codec_rereads", 0))
    writes = (breakdown.get("encode_writes", 0)
              + breakdown.get("apply_write", 0)
              + breakdown.get("payload_writes", 0))
    return {"reads": reads, "writes": writes, "total": reads + writes,
            "payload_bytes": payload, "breakdown": breakdown}


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_count(x: jax.Array, tau: jax.Array,
                 interpret: bool | None = None) -> jax.Array:
    """Number of entries with |x| >= tau (kernel-backed)."""
    interpret = _auto_interpret(interpret)
    x2d = _pad_to_blocks(x.reshape(-1).astype(jnp.float32))
    return tk.count_ge(x2d, jnp.asarray(tau, jnp.float32), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(a: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array,
             interpret: bool | None = None):
    """Selective-SSM recurrence via the Pallas kernel (kernels/ssm_scan.py).

    a, bx: (B, T, d, N) decay / input terms (the layout models/ssm.py uses);
    c: (B, T, N); h0: (B, d, N).  Returns (y (B, T, d), hT (B, d, N)).
    Pads T to BLOCK_T (identity steps: a=1, bx=0) and d to the BLOCK_D lane
    tile; transposes so d rides the 128-wide lane axis.
    """
    from repro.kernels import ssm_scan as sk
    interpret = _auto_interpret(interpret)
    B, T, d, N = a.shape
    padT = (-T) % sk.BLOCK_T
    padD = (-d) % sk.BLOCK_D

    # (B, T, d, N) -> (B, T, N, d) with lane-axis d
    a_t = jnp.pad(a.transpose(0, 1, 3, 2).astype(jnp.float32),
                  ((0, 0), (0, padT), (0, 0), (0, padD)),
                  constant_values=1.0)           # identity decay on padding
    bx_t = jnp.pad(bx.transpose(0, 1, 3, 2).astype(jnp.float32),
                   ((0, 0), (0, padT), (0, 0), (0, padD)))
    c_t = jnp.pad(c.astype(jnp.float32), ((0, 0), (0, padT), (0, 0)))
    h0_t = jnp.pad(h0.transpose(0, 2, 1).astype(jnp.float32),
                   ((0, 0), (0, 0), (0, padD)))

    y, hT = sk.ssm_scan_tiled(a_t, bx_t, c_t, h0_t, interpret=interpret)
    return y[:, :T, :d], hT[:, :, :d].transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, s0: jax.Array, interpret: bool | None = None):
    """RWKV6 wkv recurrence via the Pallas kernel (kernels/wkv6.py).

    r/k/v/logw: (B, T, H, D); u: (H, D); s0: (B, H, D, D).
    Pads T to the CHUNK tile with identity steps (logw=0, r=k=v=0).
    Returns (y (B, T, H, D), sT (B, H, D, D)).
    """
    from repro.kernels import wkv6 as wk
    interpret = _auto_interpret(interpret)
    B, T, H, D = r.shape
    padT = (-T) % wk.CHUNK

    def padt(x, val=0.0):
        return jnp.pad(x.astype(jnp.float32),
                       ((0, 0), (0, padT), (0, 0), (0, 0)),
                       constant_values=val)

    y, sT = wk.wkv6_tiled(padt(r), padt(k), padt(v), padt(logw),
                          u.astype(jnp.float32), s0.astype(jnp.float32),
                          interpret=interpret)
    return y[:, :T], sT
