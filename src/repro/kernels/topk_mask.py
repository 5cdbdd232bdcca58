"""Pallas TPU kernels for selective (top-k) masking.

TPU adaptation of the paper's per-layer top-k (DESIGN.md §3.1): instead of a
global sort we (1) build a per-octave magnitude histogram in one HBM sweep,
(2) locate the octave containing the k-th largest magnitude, (3) refine the
threshold with a few count sweeps, (4) apply ``x * (|x| >= tau)``.

All kernels tile the (flattened, padded) input as (BLOCK_ROWS, LANE) fp32
blocks in VMEM — BLOCK_ROWS=256, LANE=1024 → 1 MiB per block, well under the
~16 MiB v5e VMEM budget, with the lane dimension a multiple of 128 for the
VPU.  Reduction outputs map every grid step to the same output block; the TPU
grid is sequential so ``@pl.when(first)`` init + accumulate is safe (and
interpret mode preserves the semantics on CPU).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import NBINS, EXPO_MIN

BLOCK_ROWS = 256
LANE = 1024


def _grid_blocks(n_rows: int) -> int:
    return n_rows // BLOCK_ROWS


# --------------------------------------------------------------------------
# Kernel 1: per-octave magnitude histogram (one sweep of HBM).
# --------------------------------------------------------------------------
# Rows per factored-one-hot chunk: the (HIST_CHUNK_ROWS, NBINS/8, LANE)
# fp32 one-hot is 64 * 16 * 1024 * 4 B = 4 MiB of VMEM transient.
HIST_CHUNK_ROWS = 64
QBINS, RBINS = NBINS // 8, 8


def _factored_bin_counts(b: jax.Array) -> jax.Array:
    """(rows, LANE) bin ids (-1 = none) -> (QBINS, RBINS) fp32 counts.

    Factored one-hot: NBINS = QBINS * RBINS, bin = 8q + r.  Two narrow
    one-hots (16 + 8 compares per element instead of 128) with the bins on
    the sublane axis contract over the lane axis in one row-batched matmul
    — MXU work on TPU, and no relayout of the (rows, LANE) tile.  fp32
    accumulation is exact (chunk counts << 2^24).
    """
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (1, QBINS, 1), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (1, RBINS, 1), 1)
    # b = -1 shifts to q = -1: matches no q bin, so zeros never count.
    q_hot = ((b >> 3)[:, None, :] == q_iota).astype(jnp.float32)
    r_hot = ((b & 7)[:, None, :] == r_iota).astype(jnp.float32)
    counts = jax.lax.dot_general(
        q_hot, r_hot, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (rows, QBINS, RBINS)
    return jnp.sum(counts, axis=0)


def _hist_kernel(x_ref, hist_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    # Factored one-hot/iota bin counting, chunked over rows so the one-hot
    # transients stay in VMEM — instead of rescanning the block once per bin.
    # Each chunk is sliced on the Ref: Mosaic lowers no dynamic_slice of a
    # loaded value.
    def chunk(c, acc):
        start = pl.multiple_of(c * HIST_CHUNK_ROWS, HIST_CHUNK_ROWS)
        x = x_ref[pl.ds(start, HIST_CHUNK_ROWS), :].astype(jnp.float32)
        mag = jnp.abs(x)
        valid = mag > 0.0
        e = jnp.floor(jnp.log2(jnp.where(valid, mag, 1.0)))
        b = jnp.clip(e.astype(jnp.int32) - EXPO_MIN, 0, NBINS - 1)
        b = jnp.where(valid, b, -1)                   # zeros match no bin
        return acc + _factored_bin_counts(b)

    hist_ref[...] += jax.lax.fori_loop(
        0, BLOCK_ROWS // HIST_CHUNK_ROWS, chunk,
        jnp.zeros((QBINS, RBINS), jnp.float32)).astype(jnp.int32)


def exponent_histogram(x2d: jax.Array, *, interpret: bool) -> jax.Array:
    """x2d: (R, LANE) fp32, R multiple of BLOCK_ROWS. Returns (NBINS,) int32."""
    rows = x2d.shape[0]
    hist = pl.pallas_call(
        _hist_kernel,
        grid=(_grid_blocks(rows),),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((QBINS, RBINS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((QBINS, RBINS), jnp.int32),
        interpret=interpret,
        name="fl_topk_hist",
    )(x2d)
    return hist.reshape(NBINS)                    # bin = 8q + r, row-major


# --------------------------------------------------------------------------
# Kernel 2: count of |x| >= tau (one sweep; used by the refine loop).
# --------------------------------------------------------------------------
def _count_kernel(x_ref, tau_ref, cnt_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # Count and threshold stay (1, 1) vectors: Mosaic stores no scalars to
    # VMEM.  fp32 block sums are exact (BLOCK_ROWS * LANE < 2^24).
    x = x_ref[...].astype(jnp.float32)
    ge = (jnp.abs(x) >= tau_ref[...]).astype(jnp.float32)
    row_counts = jnp.sum(ge, axis=1, keepdims=True)              # (rows, 1)
    cnt_ref[...] += jnp.sum(row_counts, axis=0,
                            keepdims=True).astype(jnp.int32)


def count_ge(x2d: jax.Array, tau: jax.Array, *, interpret: bool) -> jax.Array:
    rows = x2d.shape[0]
    cnt = pl.pallas_call(
        _count_kernel,
        grid=(_grid_blocks(rows),),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
        name="fl_topk_count",
    )(x2d, tau.reshape(1, 1).astype(jnp.float32))
    return cnt[0, 0]


# --------------------------------------------------------------------------
# Kernel 3: apply the threshold mask (one sweep, elementwise).
# --------------------------------------------------------------------------
def _apply_kernel(x_ref, tau_ref, out_ref):
    x = x_ref[...]
    keep = (jnp.abs(x.astype(jnp.float32)) >= tau_ref[...]).astype(x.dtype)
    out_ref[...] = x * keep


def apply_threshold(x2d: jax.Array, tau: jax.Array, *, interpret: bool) -> jax.Array:
    rows = x2d.shape[0]
    return pl.pallas_call(
        _apply_kernel,
        grid=(_grid_blocks(rows),),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="fl_topk_apply",
    )(x2d, tau.reshape(1, 1).astype(jnp.float32))


# --------------------------------------------------------------------------
# Threshold selection from the histogram + refinement.
# --------------------------------------------------------------------------
def select_threshold_counts(hist: jax.Array, k: jax.Array):
    """Octave bounds [tau_lo, tau_hi) containing the k-th largest magnitude,
    plus the exact counts at both bounds.

    ``count_ge(2^(j+EXPO_MIN))`` = suffix-sum of hist from bin j; the k-th
    largest lies in the highest bin j* whose suffix count is still >= k.
    The suffix sums ARE the counts at the octave bounds, so downstream
    refinement starts with known bracket counts — no extra counting sweep.
    """
    suffix = jnp.cumsum(hist[::-1])[::-1]  # suffix[j] = count(mag >= 2^(j+EXPO_MIN))
    jstar = jnp.maximum(jnp.sum(suffix >= k) - 1, 0)
    tau_lo = jnp.exp2((jstar + EXPO_MIN).astype(jnp.float32))
    tau_hi = 2.0 * tau_lo
    suffix_ext = jnp.concatenate([suffix, jnp.zeros((1,), suffix.dtype)])
    cnt_lo = suffix_ext[jstar]
    cnt_hi = suffix_ext[jstar + 1]
    # If even the lowest bin has < k entries (k > #nonzero), keep everything
    # nonzero: threshold below the smallest representable bin.
    underfull = suffix[0] < k
    tau_lo = jnp.where(underfull, jnp.exp2(float(EXPO_MIN - 1)), tau_lo)
    cnt_lo = jnp.where(underfull, suffix[0], cnt_lo)
    return tau_lo, tau_hi, cnt_lo, cnt_hi


def select_threshold(hist: jax.Array, k: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Octave bounds only (see ``select_threshold_counts``)."""
    tau_lo, tau_hi, _, _ = select_threshold_counts(hist, k)
    return tau_lo, tau_hi
