"""On-device client update (paper Alg. 2 / Alg. 4 lines 4-8).

A client downloads the global parameters, runs ``E`` local epochs of
mini-batch SGD on its private shard, computes the parameter delta, masks it,
and uploads.  The update is pure/jit-able so the simulation can ``vmap`` it
over clients and the pod runtime can ``shard_map`` it over the data axis.

Upload semantics (see DESIGN.md §3 and EXPERIMENTS.md):

* ``"delta"`` (default): upload ``mask(W_{t+1} - W_t)``; the server applies it
  to the global model it already holds.  Information-equivalent to the
  paper's masked-weight upload (the server knows W_t and the mask indices)
  and numerically well behaved.
* ``"zero"``: the literal Alg. 4 line 14 — upload ``M ⊗ W_{t+1}`` and let the
  server average the zeroed weights.  Kept as an ablation of the paper's
  exact pseudocode.

Masking cost: with ``cfg.masking.use_kernel`` the whole delta pytree is
masked through the segmented Pallas subsystem (``ops.topk_mask_pytree``,
DESIGN.md §3.4) — ~4 HBM sweeps for the entire model instead of the
per-leaf O(L * iters) loop — which matters because this runs on every
client every round.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core.masking import MaskingConfig, mask_pytree
from repro.core.objectives import LocalObjective

PyTree = Any
LossFn = Callable[[PyTree, Any], jnp.ndarray]

__all__ = ["ClientConfig", "local_sgd", "client_update",
           "stacked_client_update", "local_update_flops"]


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    """Per-client hyperparameters: local SGD (epochs, lr, momentum), the
    mask policy applied to the delta, the upload semantics
    ("delta" | "zero"; see module docstring), and the local objective
    (none / FedProx / FedDyn — ``repro.core.objectives``)."""

    local_epochs: int = 1
    learning_rate: float = 0.01
    momentum: float = 0.0
    masking: MaskingConfig = MaskingConfig()
    upload: str = "delta"  # delta | zero
    objective: LocalObjective = LocalObjective()


def local_sgd(loss_fn: LossFn, params: PyTree, batches: Any,
              cfg: ClientConfig) -> Tuple[PyTree, jnp.ndarray]:
    """Run E epochs of SGD over ``batches`` (a pytree whose leaves have a
    leading (num_batches, ...) axis).  Returns (new_params, mean_loss)."""

    grad_fn = jax.value_and_grad(loss_fn)

    def one_step(carry, batch):
        params, vel = carry
        loss, grads = grad_fn(params, batch)
        if cfg.momentum > 0.0:
            vel = jax.tree.map(lambda v, g: cfg.momentum * v + g, vel, grads)
            step = vel
        else:
            step = grads
        params = jax.tree.map(
            lambda p, g: p - cfg.learning_rate * g.astype(p.dtype), params, step)
        return (params, vel), loss

    def one_epoch(carry, _):
        carry, losses = jax.lax.scan(one_step, carry, batches)
        return carry, jnp.mean(losses)

    vel0 = jax.tree.map(jnp.zeros_like, params)
    (params, _), losses = jax.lax.scan(
        one_epoch, (params, vel0), None, length=cfg.local_epochs)
    return params, jnp.mean(losses)


def client_update(loss_fn: LossFn, global_params: PyTree, batches: Any,
                  mask_key: jax.Array, cfg: ClientConfig,
                  residual: PyTree | None = None,
                  drift: PyTree | None = None,
                  ) -> Tuple[PyTree, PyTree, PyTree | None, jnp.ndarray]:
    """One full client round: local SGD -> delta -> (error feedback) -> mask.

    Returns ``(upload, new_residual, new_drift, mean_loss)`` where
    ``upload`` is the masked delta ("delta" semantics) or the masked local
    weights ("zero").  ``residual`` enables beyond-paper error feedback:
    masked-out mass is accumulated locally and re-added next round (pass
    None to disable, which is the paper-faithful path).  ``drift`` is the
    client's FedDyn ``h_k`` state (required iff
    ``cfg.objective.uses_drift``); ``new_drift`` is the post-round
    ``h_k − alpha·delta`` update computed on the HONEST pre-mask delta, or
    None when the objective carries no drift.
    """
    obj = cfg.objective
    local_loss = obj.localize(loss_fn, global_params, drift)
    local_params, mean_loss = local_sgd(local_loss, global_params, batches,
                                        cfg)
    delta = jax.tree.map(lambda a, b: a - b, local_params, global_params)
    new_drift = obj.update_drift(drift, delta)

    if residual is not None:
        delta = jax.tree.map(lambda d, r: d + r, delta, residual)

    with jax.named_scope("fl.mask"):
        masked = mask_pytree(mask_key, delta, cfg.masking)

    if residual is not None:
        new_residual = jax.tree.map(lambda d, m: d - m, delta, masked)
    else:
        new_residual = jax.tree.map(jnp.zeros_like, delta)

    if cfg.upload == "delta":
        upload = masked
    elif cfg.upload == "zero":
        # Literal Alg. 4: masked *weights*; zeros where the mask dropped.
        # With masking disabled nothing is dropped (a delta entry that
        # happens to be exactly 0 is NOT a masked position).
        if cfg.masking.mode == "none" or cfg.masking.gamma >= 1.0:
            upload = jax.tree.map(lambda g, d: g + d, global_params, delta)
        else:
            keep = jax.tree.map(
                lambda m: (m != 0).astype(m.dtype) if m.ndim > 0 else m,
                masked)
            upload = jax.tree.map(
                lambda g, d, k: (g + d) * k if k.ndim > 0 else g + d,
                global_params, delta, keep)
    else:
        raise ValueError(f"unknown upload semantics {cfg.upload!r}")
    return upload, new_residual, new_drift, mean_loss


def stacked_client_update(loss_fn: LossFn, global_params: PyTree,
                          stacked_batches: Any, mask_keys: jax.Array,
                          cfg: ClientConfig, stacked_residuals: PyTree,
                          error_feedback: bool,
                          stacked_drift: PyTree | None = None,
                          ) -> Tuple[PyTree, PyTree, PyTree | None,
                                     jnp.ndarray]:
    """``client_update`` vmapped over a leading client axis.

    The axis may be the full registered population (oracle round) or a
    padded cohort buffer (cohort engine, DESIGN.md §3.5) — the per-client
    math is identical, which is what the cohort/oracle equivalence tests
    rely on.  ``stacked_drift`` carries the FedDyn per-client drift rows
    (None unless ``cfg.objective.uses_drift``).  Returns stacked
    ``(uploads, new_residuals, new_drift, losses)`` with ``new_drift``
    None when the objective carries no drift.
    """

    def one_client(batches, k, res, dr):
        res_arg = res if error_feedback else None
        return client_update(loss_fn, global_params, batches, k, cfg,
                             res_arg, dr)

    return jax.vmap(one_client)(stacked_batches, mask_keys,
                                stacked_residuals, stacked_drift)


def local_update_flops(stacked_batches: Any, num_params: int,
                       cfg: ClientConfig) -> int:
    """Per-client FLOP proxy for one round: 6 * params * examples seen
    (fwd 2 + bwd 4 per parameter per example), times local epochs.

    A proxy, not an HLO count (see launch/hlo.py for that): it sets a
    client's compute time on the simulated fleet
    (``hetero.simulate_round``, the async engine's arrival clock).
    """
    leaf = jax.tree_util.tree_leaves(stacked_batches)[0]
    # leading axes: (clients, num_batches, batch, ...)
    examples = int(leaf.shape[1]) * int(leaf.shape[2])
    return 6 * int(num_params) * examples * int(cfg.local_epochs)
