"""Segmented Pallas kernels: whole-pytree selective masking (DESIGN.md §3.4).

The per-leaf pipeline (``kernels/topk_mask.py``) costs O(L * (iters + 2)) HBM
sweeps for an L-leaf model.  These kernels operate on the packed buffer from
``kernels.packing`` — every SEG_LANE-wide row belongs to exactly one segment
(leaf) — and reduce whole-model masking to a leaf-count-independent number of
sweeps:

1. ``segmented_histogram``  — (num_segments, SEG_NBINS) magnitude histogram
   (SEG_NBINS = 32 bins of OCTAVES_PER_BIN = 4 octaves each, same
   [2^EXPO_MIN, 2^(EXPO_MIN+128)) coverage as the per-leaf kernel's 128
   per-octave bins) in ONE sweep, emitted in suffix form: bin counts are
   vectorised as one compare of every element against the iota-built
   bin-edge ladder + a lane reduction, instead of a fori_loop that rescans
   the block once per bin.  Bins are 4-octave groups so the compare is 32
   wide — the first refine sweep's geometric candidates win the resolution
   back.
2. ``segmented_count``      — counts |x| >= tau for C candidate taus per
   segment per sweep, collapsing the bisection refine loop from ``iters``
   sweeps to 1-2 multi-candidate sweeps (first sweep geometric across the
   4-octave bracket, later sweeps linear).
3. ``segmented_apply``      — fused threshold-apply + kept-count in one sweep
   using the final per-segment taus.
4. ``segmented_stats``      — the histogram sweep extended with a per-segment
   max|x| reduction, so the int8 wire scale (``max|x| / 127``) rides the
   sweep that was already bracketing thresholds (DESIGN.md §10).
5. ``segmented_encode``     — the *wire-path* sweep: threshold-apply,
   optional int8 quantisation against per-segment scales, a packed 1-bit/
   element keep-bitmap, and kept counts, all emitted from ONE read of the
   packed buffer.  ``ops.topk_encode_pytree`` compacts the outputs into
   COO / bitmap payloads without ever re-reading the fp32 data.
6. ``segmented_compact``    — the COO compaction of those outputs: each
   row packed by a shift network in VMEM and appended at its running
   slot, full 1024-slot blocks written by aligned DMAs — no sort, no
   scatter, one launch for every leaf of a vmapped cohort.

Grid/tiling: each grid step processes a ``(slab_rows, SEG_LANE)`` slab.  The
per-row segment ids ride along as an (R, 1) int32 input; inside the kernel
they become a (rows, S) one-hot matrix, and every per-segment gather
(taus -> rows) and scatter (row stats -> segments) is a matmul against that
one-hot — MXU work on TPU, no dynamic indexing anywhere.  The TPU grid is
sequential, so reduction outputs map every step to the same block and use
``@pl.when(first)`` init + accumulate, like the per-leaf kernels.
``slab_rows`` trades VMEM residency against grid steps: 512 rows = 2 MiB
fp32 per slab operand for the compiled TPU path; interpret mode (CPU) uses
much larger slabs since each interpreter grid step re-stages the full
operands.

Threshold selection/refinement math (pure jnp on the tiny (S, NBINS) /
(S, C) stats, no HBM sweeps over the data) lives here too:
``select_thresholds``, ``candidate_taus`` and ``shrink_brackets``.  Counts at
both bracket ends are threaded through — the refine and final tau choice
never issue an extra counting sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NBINS, EXPO_MIN
from repro.kernels.packing import SEG_LANE

__all__ = [
    "SEG_NBINS",
    "OCTAVES_PER_BIN",
    "segmented_histogram",
    "segmented_count",
    "segmented_apply",
    "segmented_stats",
    "segmented_encode",
    "segmented_compact",
    "segmented_expand",
    "select_thresholds",
    "candidate_taus",
    "shrink_brackets",
    "pad_rows",
]

# Coarse histogram layout: SEG_NBINS bins of OCTAVES_PER_BIN octaves each,
# covering the same magnitude range as the per-leaf kernel's NBINS octaves.
OCTAVES_PER_BIN = 4
SEG_NBINS = NBINS // OCTAVES_PER_BIN

# Default slab height for the compiled TPU path: (512, 1024) fp32 = 2 MiB.
SLAB_ROWS = 512
# Rows per in-kernel chunk: bounds the one-hot transients —
# (32, SEG_LANE, SEG_NBINS) fp32 = 4 MiB — regardless of slab height.
CHUNK_ROWS = 32


def _load_chunk(ref, c) -> jax.Array:
    """Rows [c * CHUNK_ROWS, (c + 1) * CHUNK_ROWS) of a slab Ref, sliced on
    the Ref itself (Mosaic lowers no dynamic_slice of a loaded value);
    float data comes back as fp32, segment ids as int32."""
    start = pl.multiple_of(c * CHUNK_ROWS, CHUNK_ROWS)
    v = ref[pl.ds(start, CHUNK_ROWS), :]
    return v if v.dtype == jnp.int32 else v.astype(jnp.float32)


def _seg_onehot(seg: jax.Array, num_segments: int) -> jax.Array:
    """(rows, 1) int32 segment ids -> (rows, S) fp32 one-hot."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, num_segments), 1)
    return (seg == iota).astype(jnp.float32)


def _bin_ladder() -> jax.Array:
    """(1, SEG_NBINS, 1) fp32 bin-edge magnitudes 2^(EXPO_MIN + 4j).

    Comparing |x| against the ladder yields the SUFFIX form of the 4-octave
    exponent histogram (count per bin = adjacent difference) with SEG_NBINS
    plain compares per element — no log2/floor/one-hot chain — and zeros
    (incl. padding) fall below every edge, so they never count.
    """
    # Mosaic builds integer iotas only: make it int32, then cast.
    j = jax.lax.broadcasted_iota(jnp.int32, (1, SEG_NBINS, 1), 1)
    return jnp.exp2((j * OCTAVES_PER_BIN + EXPO_MIN).astype(jnp.float32))


def _row_bin_hist(x: jax.Array) -> jax.Array:
    """(rows, SEG_LANE) values -> (rows, SEG_NBINS) fp32 suffix counts:
    out[r, j] = #{e : |x[r, e]| >= 2^(EXPO_MIN + 4j)}.  fp32 sums are exact
    (row counts <= SEG_LANE).  The bins ride the sublane axis and the data
    keeps its lanes, so the (rows, SEG_NBINS, SEG_LANE) transient is dense
    in VMEM (a minor axis of SEG_NBINS would be padded to 128 lanes)."""
    ge = (jnp.abs(x)[:, None, :] >= _bin_ladder()).astype(jnp.float32)
    return jnp.sum(ge, axis=2)


# --------------------------------------------------------------------------
# Kernel 1: segmented exponent histogram — one sweep for the whole pytree.
# --------------------------------------------------------------------------
def _seg_hist_kernel(x_ref, seg_ref, hist_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    rows = x_ref.shape[0]
    S = hist_ref.shape[0]

    def chunk(c, acc):
        xc, sc = _load_chunk(x_ref, c), _load_chunk(seg_ref, c)
        row_hist = _row_bin_hist(xc)                      # (chunk, SEG_NBINS)
        seg_hot = _seg_onehot(sc, S)                      # (chunk, S)
        # scatter rows -> segments: one (S x chunk x SEG_NBINS) matmul
        return acc + jax.lax.dot_general(
            seg_hot, row_hist, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, rows // CHUNK_ROWS, chunk,
                            jnp.zeros(hist_ref.shape, jnp.float32))
    hist_ref[...] += acc.astype(jnp.int32)


def segmented_histogram(x2d: jax.Array, seg_ids: jax.Array,
                        num_segments: int, *, interpret: bool,
                        slab_rows: int | None = None) -> jax.Array:
    """x2d: (R, SEG_LANE) fp32; seg_ids: (R, 1) int32; R % slab_rows == 0.

    Returns (num_segments, SEG_NBINS) int32 per-segment 4-octave-bin
    histograms in SUFFIX form — out[s, j] = count(|x_s| >= 2^(EXPO_MIN+4j)),
    per-bin counts being adjacent differences — in one HBM sweep of the
    packed buffer.  The suffix form is exactly what ``select_thresholds``
    consumes (bracket counts come for free).
    """
    slab = _slab(x2d.shape[0], slab_rows, interpret)
    return pl.pallas_call(
        _seg_hist_kernel,
        grid=(x2d.shape[0] // slab,),
        in_specs=[
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, SEG_NBINS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, SEG_NBINS), jnp.int32),
        interpret=interpret,
        name="fl_seg_hist",
    )(x2d, seg_ids)


# --------------------------------------------------------------------------
# Kernel 2: multi-threshold segmented count — C candidates per sweep.
# --------------------------------------------------------------------------
def _seg_count_kernel(x_ref, seg_ref, taus_ref, cnt_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    rows = x_ref.shape[0]
    S, C = taus_ref.shape

    def chunk(c, acc):
        xc, sc = _load_chunk(x_ref, c), _load_chunk(seg_ref, c)
        seg_hot = _seg_onehot(sc, S)                      # (chunk, S)
        taus_row = seg_hot @ taus_ref[...]                # gather: (chunk, C)
        ge = (jnp.abs(xc)[:, None, :] >= taus_row[:, :, None]
              ).astype(jnp.float32)                       # (chunk, C, LANE)
        row_counts = jnp.sum(ge, axis=2)                  # (chunk, C)
        return acc + jax.lax.dot_general(
            seg_hot, row_counts, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, rows // CHUNK_ROWS, chunk,
                            jnp.zeros(cnt_ref.shape, jnp.float32))
    cnt_ref[...] += acc.astype(jnp.int32)


def segmented_count(x2d: jax.Array, seg_ids: jax.Array,
                    taus: jax.Array, *, interpret: bool,
                    slab_rows: int | None = None) -> jax.Array:
    """Counts of |x| >= tau per segment for ALL C candidate taus in one sweep.

    taus: (num_segments, C) fp32 (must be > 0 so padding zeros never count).
    Returns (num_segments, C) int32.
    """
    slab = _slab(x2d.shape[0], slab_rows, interpret)
    S, C = taus.shape
    return pl.pallas_call(
        _seg_count_kernel,
        grid=(x2d.shape[0] // slab,),
        in_specs=[
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, 1), lambda i: (i, 0)),
            pl.BlockSpec((S, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((S, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, C), jnp.int32),
        interpret=interpret,
        name="fl_seg_count",
    )(x2d, seg_ids, taus.astype(jnp.float32))


# --------------------------------------------------------------------------
# Kernel 3: fused per-segment threshold apply + kept-count — one sweep.
# --------------------------------------------------------------------------
def _seg_apply_kernel(x_ref, seg_ref, tau_ref, out_ref, cnt_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    S = tau_ref.shape[0]
    x = x_ref[...]
    seg_hot = _seg_onehot(seg_ref[...], S)                # (rows, S)
    tau_row = seg_hot @ tau_ref[...]                      # gather: (rows, 1)
    keep = jnp.abs(x.astype(jnp.float32)) >= tau_row      # broadcast over lane
    out_ref[...] = x * keep.astype(x.dtype)
    row_kept = jnp.sum(keep.astype(jnp.float32), axis=1, keepdims=True)
    cnt_ref[...] += jax.lax.dot_general(
        seg_hot, row_kept, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def segmented_apply(x2d: jax.Array, seg_ids: jax.Array, taus: jax.Array,
                    *, interpret: bool,
                    slab_rows: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Apply per-segment thresholds; returns (masked (R, LANE), kept (S, 1))."""
    slab = _slab(x2d.shape[0], slab_rows, interpret)
    S = taus.shape[0]
    return pl.pallas_call(
        _seg_apply_kernel,
        grid=(x2d.shape[0] // slab,),
        in_specs=[
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, 1), lambda i: (i, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
        ),
        interpret=interpret,
        name="fl_seg_apply",
    )(x2d, seg_ids, taus.reshape(S, 1).astype(jnp.float32))


# --------------------------------------------------------------------------
# Kernel 4: histogram + per-segment absmax — the stats sweep of the fused
# wire path (DESIGN.md §10).  Identical HBM traffic to segmented_histogram;
# the absmax reduction rides along so the int8 wire scale needs no extra
# sweep.
# --------------------------------------------------------------------------
def _seg_stats_kernel(x_ref, seg_ref, hist_ref, amax_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        amax_ref[...] = jnp.zeros_like(amax_ref)

    rows = x_ref.shape[0]
    S = hist_ref.shape[0]

    def chunk(c, carry):
        acc, amax = carry
        xc, sc = _load_chunk(x_ref, c), _load_chunk(seg_ref, c)
        row_hist = _row_bin_hist(xc)                      # (chunk, SEG_NBINS)
        seg_hot = _seg_onehot(sc, S)                      # (chunk, S)
        acc = acc + jax.lax.dot_general(
            seg_hot, row_hist, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # scatter-max rows -> segments: the one-hot zeroes other segments'
        # contributions, and |x| >= 0 makes max-with-zero harmless.
        # Kept as a (1, S) row: Mosaic lays out no rank-1 vectors.
        row_amax = jnp.max(jnp.abs(xc), axis=1, keepdims=True)  # (chunk, 1)
        amax = jnp.maximum(
            amax, jnp.max(seg_hot * row_amax, axis=0, keepdims=True))
        return acc, amax

    acc, amax = jax.lax.fori_loop(
        0, rows // CHUNK_ROWS, chunk,
        (jnp.zeros(hist_ref.shape, jnp.float32),
         jnp.zeros(amax_ref.shape, jnp.float32)))
    hist_ref[...] += acc.astype(jnp.int32)
    amax_ref[...] = jnp.maximum(amax_ref[...], amax)


def segmented_stats(x2d: jax.Array, seg_ids: jax.Array,
                    num_segments: int, *, interpret: bool,
                    slab_rows: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Histogram + per-segment max|x| in one sweep of the packed buffer.

    Same contract as :func:`segmented_histogram`, additionally returning the
    (num_segments, 1) fp32 per-segment absolute maximum.  Because top-k
    masking always keeps each segment's largest-magnitude entry, this absmax
    equals the masked segment's absmax — the exact quantity the int8 wire
    scale ``max|x| / 127`` needs (DESIGN.md §10), at zero extra sweeps.
    """
    slab = _slab(x2d.shape[0], slab_rows, interpret)
    hist, amax = pl.pallas_call(
        _seg_stats_kernel,
        grid=(x2d.shape[0] // slab,),
        in_specs=[
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((num_segments, SEG_NBINS), lambda i: (0, 0)),
            pl.BlockSpec((1, num_segments), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((num_segments, SEG_NBINS), jnp.int32),
            jax.ShapeDtypeStruct((1, num_segments), jnp.float32),
        ),
        interpret=interpret,
        name="fl_seg_stats",
    )(x2d, seg_ids)
    return hist, amax.reshape(num_segments, 1)


# --------------------------------------------------------------------------
# Kernel 5: fused wire-path encode — threshold-apply + int8 quantise +
# packed keep-bitmap + kept counts, all from ONE read of the packed buffer.
# --------------------------------------------------------------------------
def _bit_group_weights() -> jax.Array:
    """(SEG_LANE, SEG_LANE // 8) block-diagonal bit-packing weights.

    ``weights[i, i // 8] = 2^(i % 8)`` (zero elsewhere), so a keep-mask row
    matmul'd against it yields one byte per 8 lanes with LSB-first bit
    order — the same layout ``np.packbits(bitorder="little")`` produces.
    Sums are <= 255, exact in fp32 (and in bf16 MXU accumulation: integers
    up to 256 are representable).
    """
    i = jax.lax.broadcasted_iota(jnp.int32, (SEG_LANE, SEG_LANE // 8), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (SEG_LANE, SEG_LANE // 8), 1)
    w = jnp.exp2((i % 8).astype(jnp.float32))
    return jnp.where(i // 8 == j, w, 0.0)


def _seg_encode_kernel(x_ref, seg_ref, tau_ref, scale_ref,
                       out_ref, bm_ref, cnt_ref, *, quantize):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    S = tau_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)
    seg_hot = _seg_onehot(seg_ref[...], S)                # (rows, S)
    tau_row = seg_hot @ tau_ref[...]                      # gather: (rows, 1)
    keep = (jnp.abs(x) >= tau_row).astype(jnp.float32)
    masked = x * keep
    if quantize:
        # Same formula as compression.quantize_int8 (round then clip), with
        # the per-segment scale gathered through the one-hot — zeros stay
        # exactly zero, so the bitmap still describes the nonzero support.
        # Mosaic casts no f32 straight to a narrow int: go through int32.
        scale_row = seg_hot @ scale_ref[...]              # (rows, 1)
        q = jnp.clip(jnp.round(masked / scale_row), -127, 127)
        out_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    else:
        out_ref[...] = masked.astype(out_ref.dtype)
    bm = jax.lax.dot(keep, _bit_group_weights(),
                     preferred_element_type=jnp.float32)
    bm_ref[...] = bm.astype(jnp.int32).astype(jnp.uint8)
    row_kept = jnp.sum(keep, axis=1, keepdims=True)
    cnt_ref[...] += jax.lax.dot_general(
        seg_hot, row_kept, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def segmented_encode(x2d: jax.Array, seg_ids: jax.Array, taus: jax.Array,
                     scales: jax.Array | None = None, *, interpret: bool,
                     slab_rows: int | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fused wire-path sweep (DESIGN.md §10): one read of the packed
    buffer emits everything the upload payload needs.

    Applies the per-segment thresholds ``taus`` ((S,) fp32, > 0) and returns

    * ``out``   — (R, SEG_LANE) masked values; int8-quantised against the
      per-segment ``scales`` ((S,) fp32, > 0) when given, else the masked
      fp32 values (``segmented_apply`` semantics);
    * ``bitmap`` — (R, SEG_LANE // 8) uint8 keep-mask, LSB-first within each
      byte (byte ``b`` bit ``j`` describes lane ``8 b + j``);
    * ``kept``  — (S, 1) int32 surviving-entry counts per segment.

    The downstream COO/bitmap compaction (``ops.topk_encode_pytree``) reads
    only these outputs — 1.125 bytes/param for the int8 wire instead of the
    4 bytes/param the jnp codec path re-reads three times over.
    """
    slab = _slab(x2d.shape[0], slab_rows, interpret)
    S = taus.shape[0]
    quantize = scales is not None
    if scales is None:
        scales = jnp.ones((S,), jnp.float32)
    out_dtype = jnp.int8 if quantize else x2d.dtype
    return pl.pallas_call(
        functools.partial(_seg_encode_kernel, quantize=quantize),
        grid=(x2d.shape[0] // slab,),
        in_specs=[
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, 1), lambda i: (i, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((slab, SEG_LANE), lambda i: (i, 0)),
            pl.BlockSpec((slab, SEG_LANE // 8), lambda i: (i, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x2d.shape, out_dtype),
            jax.ShapeDtypeStruct((x2d.shape[0], SEG_LANE // 8), jnp.uint8),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
        ),
        interpret=interpret,
        name="fl_seg_encode",
    )(x2d, seg_ids, taus.reshape(S, 1).astype(jnp.float32),
      scales.reshape(S, 1).astype(jnp.float32))


# --------------------------------------------------------------------------
# Kernel 6: COO stream compaction of the fused encode's outputs — no sort
# and no scatter (DESIGN.md §10).
# --------------------------------------------------------------------------
# The output is written in groups of eight SEG_LANE-slot blocks, one
# aligned (8, SEG_LANE) tile per DMA, so each segment's slot region is
# padded to whole groups.
COMPACT_GROUP = 8 * SEG_LANE
# Rows per drop-count matmul: the (rows, SEG_LANE) x (SEG_LANE, SEG_LANE)
# triangular product fills the MXU's 128 rows.
_COMPACT_MM_ROWS = 128
# Per-row scalars: first output slot, kept count, segment region end, and
# whether the row is its segment's last.
_META = 4
_LOG_LANE = SEG_LANE.bit_length() - 1


def _compact_rows(v: jax.Array, t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Left-pack each row's kept entries with a log2(SEG_LANE)-step shift
    network.

    ``t`` holds each kept entry's count of dropped entries before it in its
    row (-1 where dropped).  Step b moves the entries whose count has bit b
    set left by 2^b; taking the bits low to high keeps the entries distinct
    and in order at every step, so no two ever meet.  Returns the moved
    values and counts: kept entry j lands at lane j - t, still carrying t.
    """
    for b in range(_LOG_LANE):
        sh = 1 << b
        inc_t = pltpu.roll(t, SEG_LANE - sh, 1)          # lane p <- p + 2^b
        inc_v = pltpu.roll(v, SEG_LANE - sh, 1)
        take = (inc_t >= 0) & ((inc_t & sh) != 0)
        stay = (t >= 0) & ((t & sh) == 0)
        t = jnp.where(take, inc_t, jnp.where(stay, t, -1))
        v = jnp.where(take, inc_v, v)
    return v, t


def _seg_compact_kernel(meta_ref, val_ref, bm_ref, base_ref, expand_ref,
                        tri_ref, _zeros_v, _zeros_i, out_v, out_i,
                        tag_ref, rows_ref, open_ref, group_ref, state, sems,
                        *, mm_rows):
    c, i = pl.program_id(0), pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SEG_LANE), 1)
    outs = (out_v, out_i)

    @pl.when((c == 0) & (i == 0))
    def _init():
        state[0] = 0                       # staging slot being filled
        state[1] = 0                       # slot 0 has a DMA in flight
        state[2] = 0                       # slot 1 has a DMA in flight
        open_ref[...] = jnp.zeros_like(open_ref)

    def dma(slot, kind, grp):
        return pltpu.make_async_copy(
            group_ref.at[slot, kind], outs[kind].at[c, pl.ds(grp * 8, 8)],
            sems.at[slot, kind])

    def wait(slot):
        for kind in range(2):
            dma(slot, kind, 0).wait()
        state[1 + slot] = 0

    def flush(grp, end):
        # Groups at or past the region's end hold slots beyond the budget:
        # shed, never written.
        @pl.when(grp * COMPACT_GROUP < end)
        def _():
            slot = state[0]
            for kind in range(2):
                dma(slot, kind, grp).start()
            state[1 + slot] = 1
            other = 1 - slot

            @pl.when(state[1 + other] == 1)
            def _():
                wait(other)
            state[0] = other

    def put(av, ai, blk, end):
        q = blk & 7
        slot = state[0]
        group_ref[slot, 0, pl.ds(q, 1), :] = av
        group_ref[slot, 1, pl.ds(q, 1), :] = ai

        @pl.when(q == 7)
        def _():
            flush(blk >> 3, end)

    def seal(av, ai, e, end):
        """Write out the segment's last, partly filled group."""
        blk, rem = e >> _LOG_LANE, e & (SEG_LANE - 1)

        @pl.when(rem != 0)
        def _():
            put(av, ai, blk, end)
        filled = (blk & 7) + (rem != 0).astype(jnp.int32)

        @pl.when((filled > 0) & (filled < 8))
        def _():
            slot = state[0]
            sub = jax.lax.broadcasted_iota(jnp.int32, (8, SEG_LANE), 0)
            for kind in range(2):
                group_ref[slot, kind] = jnp.where(
                    sub < filled, group_ref[slot, kind], 0)
            flush(blk >> 3, end)

    def merge(r, vrow, irow, carry):
        """Append row ``r``'s packed entries at its first output slot."""
        av, ai = carry
        s, n = meta_ref[0, _META * r], meta_ref[0, _META * r + 1]
        end, last = meta_ref[0, _META * r + 2], meta_ref[0, _META * r + 3]
        off = s & (SEG_LANE - 1)
        rv, ri = pltpu.roll(vrow, off, 1), pltpu.roll(irow, off, 1)
        rel = lane - off
        into_open = (rel >= 0) & (rel < n)
        into_next = (rel < 0) & (rel + SEG_LANE < n)
        av, ai = jnp.where(into_open, rv, av), jnp.where(into_open, ri, ai)
        full = off + n >= SEG_LANE

        @pl.when(full)
        def _():
            put(av, ai, s >> _LOG_LANE, end)
        av = jnp.where(full, jnp.where(into_next, rv, 0), av)
        ai = jnp.where(full, jnp.where(into_next, ri, 0), ai)

        @pl.when(last == 1)
        def _():
            seal(av, ai, s + n, end)
        done = last == 1
        return jnp.where(done, 0, av), jnp.where(done, 0, ai)

    def mm_chunk(m, carry):
        r0 = pl.multiple_of(m * mm_rows, mm_rows)
        # keep bits per lane: each bitmap byte spread over its 8 lanes
        byte = bm_ref[pl.ds(r0, mm_rows), :].astype(jnp.int32)
        lane_byte = jax.lax.dot(
            byte.astype(jnp.float32).astype(jnp.bfloat16), expand_ref[...],
            preferred_element_type=jnp.float32).astype(jnp.int32)
        mm_lane = jax.lax.broadcasted_iota(jnp.int32, (mm_rows, SEG_LANE), 1)
        keep = (lane_byte >> (mm_lane & 7)) & 1
        # dropped entries before each lane: 0/1 products, exact in f32
        drops = jax.lax.dot(
            (1 - keep).astype(jnp.bfloat16), tri_ref[...],
            preferred_element_type=jnp.float32).astype(jnp.int32)
        tag_ref[...] = jnp.where(keep == 1, drops, -1)

        def rows8(j, carry):
            rr = pl.multiple_of(j * 8, 8)
            v, t = _compact_rows(val_ref[pl.ds(r0 + rr, 8), :],
                                 tag_ref[pl.ds(rr, 8), :])
            kept = t >= 0
            rows_ref[0] = jnp.where(kept, v, 0)
            rows_ref[1] = jnp.where(
                kept, base_ref[pl.ds(r0 + rr, 8), :] + lane + t, 0)

            def row(q, carry):
                return merge(r0 + rr + q, rows_ref[0, pl.ds(q, 1), :],
                             rows_ref[1, pl.ds(q, 1), :], carry)

            return jax.lax.fori_loop(0, 8, row, carry)

        return jax.lax.fori_loop(0, mm_rows // 8, rows8, carry)

    carry = jax.lax.fori_loop(0, val_ref.shape[0] // mm_rows, mm_chunk,
                              (open_ref[0:1, :], open_ref[1:2, :]))
    open_ref[0:1, :], open_ref[1:2, :] = carry

    @pl.when((c == pl.num_programs(0) - 1) & (i == pl.num_programs(1) - 1))
    def _drain():
        for slot in range(2):
            @pl.when(state[1 + slot] == 1)
            def _():
                wait(slot)


def _fold_vmap(call):
    """Wrap ``call`` (arrays with a leading client axis -> tuple of such
    arrays) so that ``jax.vmap`` folds the mapped axis into the client axis:
    a vmapped cohort becomes ONE kernel launch with a client grid axis."""
    @jax.custom_batching.custom_vmap
    def fn(*xs):
        return call(*xs)

    @fn.def_vmap
    def _rule(axis_size, in_batched, *xs):
        xs = [x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
              for x, b in zip(xs, in_batched)]
        outs = fn(*[x.reshape(-1, *x.shape[2:]) for x in xs])
        outs = tuple(o.reshape(axis_size, -1, *o.shape[1:]) for o in outs)
        return outs, (True,) * len(outs)

    return fn


def _compact_call(meta, vals, bitmap, *, lane_base, num_blocks, slab,
                  interpret):
    B, R, L = vals.shape
    mm_rows = _COMPACT_MM_ROWS if slab % _COMPACT_MM_ROWS == 0 else CHUNK_ROWS
    r = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = (r < col).astype(jnp.bfloat16)                  # strict upper
    expand = (col[:L // 8] // 8 == r[:L // 8]).astype(jnp.bfloat16)
    out_shape = jax.ShapeDtypeStruct((B, num_blocks, L), jnp.int32)
    zeros = [jnp.zeros(out_shape.shape, jnp.int32) for _ in range(2)]
    return pl.pallas_call(
        functools.partial(_seg_compact_kernel, mm_rows=mm_rows),
        grid=(B, R // slab),
        in_specs=[
            pl.BlockSpec((None, None, 1, _META * slab),
                         lambda c, i: (c, i, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((None, slab, L), lambda c, i: (c, i, 0)),
            pl.BlockSpec((None, slab, L // 8), lambda c, i: (c, i, 0)),
            pl.BlockSpec((slab, 1), lambda c, i: (i, 0)),
            pl.BlockSpec((L // 8, L), lambda c, i: (0, 0)),
            pl.BlockSpec((L, L), lambda c, i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
        out_shape=(out_shape, out_shape),
        scratch_shapes=[
            pltpu.VMEM((mm_rows, L), jnp.int32),          # drop counts
            pltpu.VMEM((2, 8, L), jnp.int32),             # packed rows
            pltpu.VMEM((2, L), jnp.int32),                # open block
            pltpu.VMEM((2, 2, 8, L), jnp.int32),          # group staging
            pltpu.SMEM((3,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="fl_seg_compact",
    )(meta.reshape(B, R // slab, 1, _META * slab), vals, bitmap, lane_base,
      expand, tri, *zeros)


def segmented_compact(vals: jax.Array, bitmap: jax.Array, spec,
                      budgets, *, interpret: bool,
                      slab_rows: int | None = None
                      ) -> list[tuple[jax.Array, jax.Array]]:
    """Stream-compact the fused encode's outputs into per-segment COO slots.

    ``vals``: (R, SEG_LANE) f32 or int8 values and ``bitmap``:
    (R, SEG_LANE // 8) uint8 keep-bits, both as :func:`segmented_encode`
    wrote them over ``spec``'s packed rows (R a slab multiple).
    ``budgets``: static slot count k per segment.

    Returns, per segment, ``(indices, values)``: (k,) int32 arrays holding
    the kept entries in index order (index = position in the leaf), the
    first k kept where more were kept (shed by highest index), zeros in
    the slots nothing filled: exactly the cumsum + scatter reference
    ``ops.coo_compact_reference``.

    Each SEG_LANE row is packed by a shift network in VMEM; a row's first
    output slot comes from a per-segment running count of kept entries,
    computed ahead on the (R,) row popcounts.  The packed row is rotated to
    that slot's lane and merged into the open block; full blocks gather in
    an (8, SEG_LANE) staging tile that goes to HBM by one aligned DMA.
    Values cross the kernel as 32-bit words: f32 bit patterns, or int8
    widened to int32 and narrowed after.  Under ``jax.vmap`` the mapped
    axis becomes the kernel's client grid axis.
    """
    narrow = vals.dtype == jnp.int8
    words = (vals.astype(jnp.int32) if narrow
             else jax.lax.bitcast_convert_type(vals, jnp.int32))
    R = vals.shape[0]
    slab = _slab(R, slab_rows, interpret)
    seg = spec.seg_ids()[:, 0]
    regions = [-(-k // COMPACT_GROUP) * COMPACT_GROUP for k in budgets]
    region_base = np.cumsum([0] + regions)
    first_row = np.asarray([ls.offset // SEG_LANE for ls in spec.leaves])
    last_row = first_row + np.asarray([ls.num_rows for ls in spec.leaves]) - 1
    static = np.zeros((R, _META), np.int32)       # padding rows: no-ops
    static[:spec.rows, 0] = region_base[seg]
    static[:spec.rows, 2] = region_base[seg + 1]
    static[last_row, 3] = 1
    lane_base = np.zeros((R, 1), np.int32)
    lane_base[:spec.rows, 0] = (np.arange(spec.rows)
                                - first_row[seg]) * SEG_LANE
    seg_first = np.zeros((R,), np.int32)
    seg_first[:spec.rows] = first_row[seg]

    kept = jnp.sum(jax.lax.population_count(bitmap).astype(jnp.int32),
                   axis=1)
    before = jnp.cumsum(kept) - kept
    zero = jnp.zeros_like(kept)
    dyn = jnp.stack([before - before[seg_first], kept, zero, zero], axis=1)
    meta = (dyn + static).reshape(-1)

    call = functools.partial(
        _compact_call, lane_base=jnp.asarray(lane_base),
        num_blocks=int(region_base[-1]) // SEG_LANE, slab=slab,
        interpret=interpret)
    out_v, out_i = _fold_vmap(call)(meta[None], words[None], bitmap[None])
    flat_v = out_v[0].reshape(-1)
    flat_v = (flat_v.astype(jnp.int8) if narrow
              else jax.lax.bitcast_convert_type(flat_v, vals.dtype))
    flat_i = out_i[0].reshape(-1)
    return [(jax.lax.slice_in_dim(flat_i, b, b + k),
             jax.lax.slice_in_dim(flat_v, b, b + k))
            for b, k in zip(region_base[:-1].tolist(), budgets)]


# --------------------------------------------------------------------------
# Kernel 7: COO expand — the inverse of the compaction: each leaf's
# (indices, values) slots back to its dense tensor, with no sort and no
# scatter (DESIGN.md §10).
# --------------------------------------------------------------------------
# The expand's rows are one vreg wide: Mosaic lowers a slice of a VMEM
# window at a dynamic, unaligned row offset only for 128-lane rows, and a
# leaf whose last axis is 128 then comes out of the buffer as it is laid
# out.  A group is one COMPACT_GROUP-element output tile.
EXPAND_LANE = 128
_LOG_EXPAND_LANE = EXPAND_LANE.bit_length() - 1
_EXPAND_ROWS = COMPACT_GROUP // EXPAND_LANE
_LOG_GROUP = COMPACT_GROUP.bit_length() - 1
# A group's slots span at most _EXPAND_ROWS + 1 slot rows from a start
# row that the window's 8-aligned first row puts within its first 8.
_EXPAND_WIN = _EXPAND_ROWS + 8


def _flat_shift(x: jax.Array, s: int) -> jax.Array:
    """Move every entry of an (rows, EXPAND_LANE) tile ``s`` places on in
    its row-major order, row ends spilling into the next row."""
    if s >= EXPAND_LANE:
        return pltpu.roll(x, s // EXPAND_LANE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    y = pltpu.roll(x, s, 1)
    return jnp.where(lane >= s, y, pltpu.roll(y, 1, 0))


def _expand_tile(v: jax.Array, t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Spread a group's run of slots to their places: the mirror of
    :func:`_compact_rows`.

    ``t`` holds each live entry's distance to its place, its index less
    its rank in the run (-1 where dead).  Indices ascend, so ``t`` never
    falls along the run.  Step b moves the entries whose distance has bit
    b set on by 2^b; taking the bits high to low keeps the entries
    distinct and in order at every step, and none passes the tile's end.
    Returns the moved values and distances: entry m lands at m + t.
    """
    for b in reversed(range(_LOG_GROUP)):
        sh = 1 << b
        inc_t, inc_v = _flat_shift(t, sh), _flat_shift(v, sh)
        take = (inc_t >= 0) & ((inc_t & sh) != 0)
        stay = (t >= 0) & ((t & sh) == 0)
        t = jnp.where(take, inc_t, jnp.where(stay, t, -1))
        v = jnp.where(take, inc_v, v)
    return v, t


def _seg_expand_kernel(meta_ref, idx_hbm, val_hbm, out_ref, win_i, win_v,
                       sems):
    c = pl.program_id(0)
    groups = out_ref.shape[0] // _EXPAND_ROWS
    shape = (_EXPAND_ROWS, EXPAND_LANE)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rank = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * EXPAND_LANE + lane

    def window(g, slot):
        r0 = pl.multiple_of(meta_ref[0, _META * g], 8)
        return [pltpu.make_async_copy(src.at[c, pl.ds(r0, _EXPAND_WIN)],
                                      dst.at[slot], sems.at[slot, kind])
                for kind, (src, dst) in enumerate(((idx_hbm, win_i),
                                                   (val_hbm, win_v)))]

    for copy in window(0, 0):
        copy.start()

    def group(g, carry):
        slot = g & 1

        @pl.when(g + 1 < groups)
        def _():
            for copy in window(g + 1, 1 - slot):
                copy.start()
        for copy in window(g, slot):
            copy.wait()
        u, n = meta_ref[0, _META * g + 1], meta_ref[0, _META * g + 2]
        e0 = meta_ref[0, _META * g + 3]
        q0, off = u >> _LOG_EXPAND_LANE, u & (EXPAND_LANE - 1)
        rot = (EXPAND_LANE - off) & (EXPAND_LANE - 1)

        def run(ref):
            """The group's slots from window slot ``u`` on, at rank 0."""
            a = pltpu.roll(ref[slot, pl.ds(q0, _EXPAND_ROWS), :], rot, 1)
            b = pltpu.roll(ref[slot, pl.ds(q0 + 1, _EXPAND_ROWS), :], rot, 1)
            return jnp.where(lane < EXPAND_LANE - off, a, b)

        t = jnp.where(rank < n, run(win_i) - e0 - rank, -1)
        v, t = _expand_tile(run(win_v), t)
        # ±0 both land as +0, as the reference's add into zeros gives.
        row0 = pl.multiple_of(g * _EXPAND_ROWS, _EXPAND_ROWS)
        out_ref[pl.ds(row0, _EXPAND_ROWS), :] = jnp.where(
            (t >= 0) & (v != 0), v, 0.0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


def _expand_call(meta, idx, vals, *, rows, slab, interpret):
    B = idx.shape[0]
    return (pl.pallas_call(
        _seg_expand_kernel,
        grid=(B, rows // slab),
        in_specs=[
            pl.BlockSpec((None, None, 1, _META * slab // _EXPAND_ROWS),
                         lambda c, i: (c, i, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, slab, EXPAND_LANE),
                               lambda c, i: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows, EXPAND_LANE), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, _EXPAND_WIN, EXPAND_LANE), jnp.int32),
            pltpu.VMEM((2, _EXPAND_WIN, EXPAND_LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="fl_seg_expand",
    )(meta.reshape(B, rows // slab, 1, -1), idx, vals),)


def segmented_expand(coo, spec, *, interpret: bool,
                     slab_rows: int | None = None) -> list[jax.Array]:
    """Expand each leaf's COO slots back to its dense tensor: the inverse
    of :func:`segmented_compact`.

    ``coo``: per leaf of ``spec``, ``(indices, values)`` (k,) arrays laid
    out as the compaction writes them: the live slots (slot 0, and every
    slot whose index is above 0) first, indices strictly ascending, then
    (0, 0) pads.  Returns the dense leaves, ``spec``'s shapes in the
    values' dtype: exactly ``compression.decode_sparse`` of each payload,
    which adds every slot into a zeroed tensor.

    Each leaf's output is cut into COMPACT_GROUP-element tiles.  The live
    slots of a tile form one run (ascending indices), found by a binary
    search of the tile's first index over the slots, outside the kernel.
    The kernel brings each run in with a DMA of its slot rows, rotates it
    to rank 0 and spreads it to its places with a shift network
    (:func:`_expand_tile`), writing every element of the tile.  Values
    cross as f32.  Under ``jax.vmap`` the mapped axis becomes the
    kernel's client grid axis: a cohort is one launch.
    """
    groups = [-(-ls.size // COMPACT_GROUP) for ls in spec.leaves]
    slot_base = np.cumsum([0] + [idx.shape[0] for idx, _ in coo])
    slab = _slab(sum(groups) * 8, slab_rows, interpret) // 8
    total = -(-sum(groups) // slab) * slab

    starts, counts = [], []
    for (idx, _), g, base in zip(coo, groups, slot_base[:-1].tolist()):
        live = (idx > 0) | (jnp.arange(idx.shape[0]) == 0)
        key = jnp.where(live, idx, jnp.iinfo(jnp.int32).max)
        before = jnp.searchsorted(
            key, jnp.arange(g + 1, dtype=jnp.int32) * COMPACT_GROUP,
            method="scan").astype(jnp.int32)
        starts.append(base + before[:-1])
        counts.append(before[1:] - before[:-1])
    pad = jnp.zeros((total - sum(groups),), jnp.int32)
    start = jnp.concatenate(starts + [pad])
    r0 = (start >> (_LOG_EXPAND_LANE + 3)) << 3
    first = np.zeros((total,), np.int32)
    first[:sum(groups)] = np.concatenate(
        [np.arange(g, dtype=np.int32) * COMPACT_GROUP for g in groups])
    meta = jnp.stack([r0, start - r0 * EXPAND_LANE,
                      jnp.concatenate(counts + [pad]), jnp.asarray(first)],
                     axis=1).reshape(-1)

    # r0 + _EXPAND_WIN slot rows must exist for every group's window.
    slot_rows = (8 * (int(slot_base[-1]) >> (_LOG_EXPAND_LANE + 3))
                 + _EXPAND_WIN)
    tail = slot_rows * EXPAND_LANE - int(slot_base[-1])

    def slots(parts, dtype):
        flat = jnp.concatenate([p.astype(dtype) for p in parts]
                               + [jnp.zeros((tail,), dtype)])
        return flat.reshape(slot_rows, EXPAND_LANE)

    call = functools.partial(_expand_call, rows=total * _EXPAND_ROWS,
                             slab=slab * _EXPAND_ROWS, interpret=interpret)
    (out,) = _fold_vmap(call)(
        meta[None], slots([i for i, _ in coo], jnp.int32)[None],
        slots([v for _, v in coo], jnp.float32)[None])

    def leaf(b, ls):
        # Whole rows slice out as they lie; a flat slice re-tiles.
        if ls.size % EXPAND_LANE == 0:
            return jax.lax.slice_in_dim(out[0], b // EXPAND_LANE,
                                        (b + ls.size) // EXPAND_LANE)
        return jax.lax.slice_in_dim(out[0].reshape(-1), b, b + ls.size)

    bases = (np.cumsum([0] + groups) * COMPACT_GROUP).tolist()
    return [leaf(b, ls).reshape(ls.shape).astype(v.dtype)
            for b, ls, (_, v) in zip(bases, spec.leaves, coo)]


# --------------------------------------------------------------------------
# Slab sizing + row padding.
# --------------------------------------------------------------------------
# Interpret mode re-stages the FULL operands once per interpreter grid step,
# so its wall-clock is ~ grid_steps * buffer_bytes: use one huge slab.  The
# compiled TPU path is VMEM-bound: default (512, 1024) fp32 = 2 MiB slabs.
INTERPRET_SLAB_ROWS = 16384


def _slab(total_rows: int, slab_rows: int | None, interpret: bool) -> int:
    if slab_rows is None:
        slab_rows = INTERPRET_SLAB_ROWS if interpret else SLAB_ROWS
    # A slab never exceeds the (chunk-rounded) buffer and always divides into
    # whole CHUNK_ROWS chunks — a user value is rounded DOWN to the chunk
    # multiple (floor, never below one chunk), else the kernels' chunk loops
    # would silently skip the slab tail; pad_rows pads to a slab multiple.
    slab_rows = max(CHUNK_ROWS, slab_rows - slab_rows % CHUNK_ROWS)
    rounded = -(-total_rows // CHUNK_ROWS) * CHUNK_ROWS
    return min(slab_rows, rounded)


def pad_rows(x2d: jax.Array, seg_ids: jax.Array, *, interpret: bool,
             slab_rows: int | None = None):
    """Pad the packed buffer with zero rows to a whole number of slabs.

    Padding rows get segment id 0; all-zero rows contribute to no histogram
    bin, no count (taus > 0), and mask to zeros — they are invisible.
    """
    slab = _slab(max(x2d.shape[0], 1), slab_rows, interpret)
    pad = (-x2d.shape[0]) % slab
    if pad == 0:
        return x2d, seg_ids
    x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    seg_ids = jnp.pad(seg_ids, ((0, pad), (0, 0)))
    return x2d, seg_ids


# --------------------------------------------------------------------------
# Threshold selection + multi-candidate bracket refinement (pure jnp; operates
# on (S, *) statistics only — no sweeps over the packed data).
# --------------------------------------------------------------------------
def select_thresholds(suffix: jax.Array, k: jax.Array):
    """Vectorised magnitude bracketing for every segment at once.

    suffix: (S, SEG_NBINS) int32 suffix-form histogram from
    ``segmented_histogram`` (suffix[s, j] = count at bin edge j); k: (S,)
    int32.  Returns ``(lo, hi, cnt_lo, cnt_hi)`` — per-segment 4-octave
    bounds [lo, hi) containing the k-th largest magnitude plus the EXACT
    counts at both ends, so refinement starts with known bracket counts and
    never needs an extra counting sweep.
    """
    S = suffix.shape[0]
    rows = jnp.arange(S)
    jstar = jnp.maximum(jnp.sum(suffix >= k[:, None], axis=1) - 1, 0)
    lo = jnp.exp2((jstar * OCTAVES_PER_BIN + EXPO_MIN).astype(jnp.float32))
    hi = float(2 ** OCTAVES_PER_BIN) * lo
    suffix_ext = jnp.concatenate(
        [suffix, jnp.zeros((S, 1), suffix.dtype)], axis=1)
    cnt_lo = suffix_ext[rows, jstar]
    cnt_hi = suffix_ext[rows, jstar + 1]
    # k exceeds the number of nonzeros: keep everything nonzero by dropping
    # the lower bound below the smallest representable bin.
    underfull = suffix[:, 0] < k
    lo = jnp.where(underfull, jnp.exp2(float(EXPO_MIN - 1)), lo)
    cnt_lo = jnp.where(underfull, suffix[:, 0], cnt_lo)
    return lo, hi, cnt_lo, cnt_hi


def candidate_taus(lo: jax.Array, hi: jax.Array, num: int,
                   geometric: bool = False) -> jax.Array:
    """(S, num) interior candidate thresholds of each [lo, hi] bracket.

    ``geometric`` spaces candidates by constant RATIO — right for the first
    refine over the histogram's 4-octave (16x) bracket, where linear spacing
    would waste most candidates on the top octave.  Later sweeps over narrow
    brackets use linear spacing.
    """
    frac = (jnp.arange(1, num + 1, dtype=jnp.float32) / (num + 1.0))
    if geometric:
        ratio = jnp.exp(frac[None, :] * jnp.log(hi / lo)[:, None])
        return lo[:, None] * ratio
    return lo[:, None] + frac[None, :] * (hi - lo)[:, None]


def shrink_brackets(lo, hi, cnt_lo, cnt_hi, cand, counts, k):
    """Tighten every segment's bracket around the k-th magnitude.

    ``cand``/``counts``: (S, C) ascending candidate taus and their counts
    from one ``segmented_count`` sweep.  Counts are non-increasing along the
    extended grid [lo, cand..., hi], so the number of entries with count > k
    locates the tightest bracket; counts at the new ends come for free.
    """
    ext_taus = jnp.concatenate([lo[:, None], cand, hi[:, None]], axis=1)
    ext_cnts = jnp.concatenate(
        [cnt_lo[:, None], counts, cnt_hi[:, None]], axis=1)
    C2 = ext_taus.shape[1]
    num_gt = jnp.sum(ext_cnts > k[:, None], axis=1)
    lo_idx = jnp.clip(num_gt - 1, 0, C2 - 1)
    hi_idx = jnp.clip(num_gt, 0, C2 - 1)
    rows = jnp.arange(ext_taus.shape[0])
    return (ext_taus[rows, lo_idx], ext_taus[rows, hi_idx],
            ext_cnts[rows, lo_idx], ext_cnts[rows, hi_idx])
