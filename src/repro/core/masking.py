"""Parameter masking (paper §3.2.1 random, §4.2 selective top-k).

Terminology follows the paper: the *masking rate* ``gamma`` is the fraction of
parameters KEPT (Fig. 4: "masking rate 0.1" discards 90%).

Two selection semantics are provided:

* ``selective_mask_exact``    — exact per-leaf top-k via sort (the paper's
  Alg. 4 as written; the jnp oracle).
* ``selective_mask_threshold``— TPU-native threshold-bisection top-k (see
  DESIGN.md §3.1): static shapes, scan/jit/pjit-safe, backed by the Pallas
  kernels in ``repro.kernels`` on TPU and by pure jnp elsewhere.

Both operate on a *delta* pytree (W_{t+1} - W_t per Alg. 4 line 11) and return
the masked delta plus bookkeeping for byte accounting.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

__all__ = [
    "MaskingConfig",
    "random_mask",
    "selective_mask_exact",
    "selective_mask_threshold",
    "mask_pytree",
]


@dataclasses.dataclass(frozen=True)
class MaskingConfig:
    """gamma: fraction kept; mode: none|random|selective; min_leaf_size:
    leaves smaller than this (biases, norms) are always sent dense — masking a
    10-element bias saves nothing and harms convergence."""

    gamma: float = 1.0
    mode: str = "none"  # none | random | selective
    min_leaf_size: int = 256
    bisect_iters: int = 24
    use_kernel: bool = False  # route through the Pallas kernel path


def _kept_count(size: int, gamma: float) -> int:
    return max(1, int(round(gamma * size)))


def _refine_sweeps_for(iters: int) -> int:
    """Map a bisection iteration budget onto segmented refine sweeps: each
    multi-candidate sweep resolves ~4 bits of threshold vs 1 per bisection
    iter, so the default 24 iters ~ 2 sweeps, 48 iters ~ 4."""
    return max(2, min(4, iters // 12))


def random_mask(key: jax.Array, delta: jax.Array, gamma: float) -> jax.Array:
    """Paper Alg. 2: keep a Bernoulli(gamma) subset of entries.

    The paper's ``randi`` draws a fixed *proportion*; we use an exact-count
    random mask (permutation-based) so the kept fraction is deterministic —
    matters for fair byte accounting at small leaves.
    """
    flat = delta.reshape(-1)
    k = _kept_count(flat.size, gamma)
    scores = jax.random.uniform(key, flat.shape)
    # Single top_k pass (O(n log k)) instead of the double argsort ranking:
    # the k lowest-score positions form an exact-count uniform subset.
    _, idx = jax.lax.top_k(-scores, k)
    keep = jnp.zeros(flat.shape, delta.dtype).at[idx].set(1)
    return (flat * keep).reshape(delta.shape)


def selective_mask_exact(delta: jax.Array, gamma: float) -> jax.Array:
    """Paper Alg. 4: keep the k = gamma*|W| entries of largest |delta|.

    Exact semantics via full sort; O(n log n) — the reference/oracle path.
    """
    flat = delta.reshape(-1)
    k = _kept_count(flat.size, gamma)
    mag = jnp.abs(flat)
    # kth largest magnitude; keep strictly-greater plus enough ties.
    thresh = jnp.sort(mag)[flat.size - k]
    keep = mag >= thresh
    # Tie handling: if ties push the kept count above k, drop surplus ties by
    # index order to keep exactly k (matches a stable top-k).
    surplus = jnp.cumsum(keep) > k
    keep = keep & ~surplus
    return (flat * keep.astype(delta.dtype)).reshape(delta.shape)


def threshold_for_topk(mag: jax.Array, k: jax.Array, iters: int = 24) -> jax.Array:
    """Find tau such that count(mag >= tau) ≈ k by bisection.

    Pure element-wise compares + reductions (VPU friendly, static shapes).
    Accuracy: after ``iters`` halvings of [0, max], the kept count is within
    the number of entries falling in one 2^-iters-wide magnitude bin —
    property-tested against the sort oracle in tests/test_masking.py.
    """
    mag = mag.reshape(-1).astype(jnp.float32)
    hi = jnp.max(mag) + 1e-12
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum(mag >= mid)
        # too many kept -> raise threshold (lo = mid); too few -> lower hi.
        lo = jnp.where(count > k, mid, lo)
        hi = jnp.where(count > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi  # hi always satisfies count(mag >= hi) <= k (conservative)


def selective_mask_threshold(delta: jax.Array, gamma: float,
                             iters: int = 24,
                             use_kernel: bool = False) -> jax.Array:
    """TPU-native selective masking: threshold-bisection top-k (DESIGN.md §3.1).

    When ``use_kernel`` is set, the array is routed through the segmented
    Pallas path (``ops.topk_mask_pytree`` on a single-leaf tree, DESIGN.md
    §3.4): compiled through Mosaic on a TPU, run in the Pallas interpreter
    on any other backend (the CPU tests).  ``iters`` maps onto the
    number of multi-candidate refine sweeps (each sweep resolves ~4 bits of
    threshold, vs 1 bit per bisection iter), so higher ``iters`` still buys
    tighter thresholds on the kernel path.
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.topk_mask_pytree(delta, gamma, min_leaf_size=0,
                                     refine_sweeps=_refine_sweeps_for(iters))
    flat = delta.reshape(-1)
    k = jnp.asarray(_kept_count(flat.size, gamma), jnp.int32)
    tau = threshold_for_topk(jnp.abs(flat), k, iters)
    keep = (jnp.abs(flat) >= tau).astype(delta.dtype)
    return (flat * keep).reshape(delta.shape)


def mask_pytree(key: jax.Array, delta: PyTree, cfg: MaskingConfig) -> PyTree:
    """Apply the configured masking to a delta pytree (Alg. 2/4 layer loop).

    Small leaves (< cfg.min_leaf_size) pass through dense.  Returns the masked
    delta pytree with the same structure/dtypes.

    Selective masking with ``cfg.use_kernel`` routes the WHOLE pytree through
    the segmented Pallas subsystem (``ops.topk_mask_pytree``, DESIGN.md
    §3.4): a leaf-count-independent ~4 HBM sweeps instead of the per-leaf
    O(L * iters) pipeline below.
    """
    if cfg.mode == "none" or cfg.gamma >= 1.0:
        return delta

    if cfg.mode == "selective" and cfg.use_kernel:
        from repro.kernels import ops as kops
        return kops.topk_mask_pytree(
            delta, cfg.gamma, min_leaf_size=cfg.min_leaf_size,
            refine_sweeps=_refine_sweeps_for(cfg.bisect_iters))

    leaves, treedef = jax.tree_util.tree_flatten(delta)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, leaf_key in zip(leaves, keys):
        if leaf.size < cfg.min_leaf_size:
            out.append(leaf)
        elif cfg.mode == "random":
            out.append(random_mask(leaf_key, leaf, cfg.gamma))
        elif cfg.mode == "selective":
            # use_kernel was handled by the whole-pytree route above; this
            # per-leaf loop is always the pure-jnp path.
            out.append(selective_mask_threshold(
                leaf, cfg.gamma, cfg.bisect_iters))
        else:
            raise ValueError(f"unknown masking mode {cfg.mode!r}")
    return jax.tree_util.tree_unflatten(treedef, out)
