"""The federated round — simulation (vmap over clients) form.

``make_federated_round`` builds one jit-able function implementing paper
Alg. 1/3 server loop body + Alg. 2/4 client bodies:

  1. draw the participation mask from the sampling schedule (static/dynamic),
  2. every registered client runs its local update (vmap) — non-participants
     are masked out of the aggregation, which keeps shapes static,
  3. weighted FedAvg (Eq. 2): Θ_{t+1} = Θ_t + Σ_i w_i · upload_i with
     w_i = mask_i·n_i / Σ mask_j·n_j.

Note on Eq. 1/2: the paper writes an extra 1/m in front of Σ (n_i/n)Θ^i; since
the n_i/n weights already sum to 1 over the selected set, the extra 1/m would
shrink the model m-fold.  We take Σ (n_i/n)Θ^i, which matches FedAvg
(McMahan et al.) and the paper's cited behaviour.

Two execution forms of the same round:

* **oracle** (``make_federated_round``): vmap over ALL registered clients,
  non-participants zero-weighted — simple, flat in c(t);
* **cohort engine** (``make_cohort_round`` / ``make_cohort_scan``): gather
  only the sampled m_t clients into a bucketed padded cohort buffer, run
  client_update over the cohort axis, scatter residuals back (DESIGN.md
  §3.5) — per-round work decays with c(t).

Every builder takes two further scenario axes (DESIGN.md §5):

* ``sampler`` — a :class:`repro.core.sampling.ClientSampler` picking WHICH
  m_t clients and the aggregation weights that keep the weighted mean
  unbiased under non-uniform selection.  Adaptive samplers (importance /
  threshold) consume and emit a per-client norm-tracker vector, so the
  round signature gains a ``norms`` state argument/result.
* ``hetero`` — a :class:`repro.core.hetero.HeteroModel`; its per-client
  drop rates are drawn INSIDE the round (a dropped upload is zero-weighted
  and, under error feedback, leaves that client's residual untouched), so
  both engines agree bit-exactly on which uploads count.

The pod (shard_map) form of the same round lives in
``repro.launch.fedtrain`` — identical math, collectives instead of vmap.

Every round body names its layers with ``jax.named_scope``, so each op's
``op_name`` (and a device trace) says which layer it belongs to:
``fl.local_update`` (selection, batch gathers, the client updates),
``fl.mask`` (inside it, ``client.client_update``), ``fl.encode`` /
``fl.decode`` (``UploadCodec.roundtrip``) and ``fl.aggregate`` (the
quarantine gate through the metrics).  Scopes change metadata only.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.attacks import attack_keys
from repro.core.client import ClientConfig, stacked_client_update
from repro.core.codecs import roundtrip_stacked
from repro.core.sampling import (SamplingSchedule, UniformSampler,
                                 participation_mask)

PyTree = Any

__all__ = ["FederatedConfig", "make_federated_round", "make_cohort_round",
           "make_cohort_scan", "make_cohort_compute", "cohort_select",
           "fedavg_aggregate", "make_store_selection", "make_store_compute",
           "make_store_round", "StoreRound"]


def _resolve_policies(codec, aggregator, normalize: bool = True):
    """Normalize the optional (codec, aggregator) pair every round builder
    takes: identity wire + plain fedavg when unset.

    ``normalize`` binds the sampler's weight semantics into the returned
    aggregation call.  Legacy aggregators registered against the PR-4
    4-argument ``fn(params, uploads, weights, semantics)`` contract keep
    working under self-normalizing samplers; pairing one with a
    Horvitz-Thompson sampler (``normalize=False``) raises at build time
    instead of silently re-normalizing the debiased weights.  Aggregators
    that declare ``ht_compatible=False`` (Krum-family: selection ignores
    weight magnitudes, so HT debiasing cannot reach the estimate) likewise
    raise at build time when paired with an HT sampler.
    """
    if not normalize and aggregator is not None and not getattr(
            aggregator, "ht_compatible", True):
        raise TypeError(
            f"aggregator {aggregator.name!r} is not Horvitz-Thompson "
            "compatible but the sampler emits HT weights (normalize="
            "False); use a weighted-rank aggregator (coordinate_median / "
            "trimmed_mean) or a self-normalizing sampler")
    fn = aggregator.fn if aggregator is not None else fedavg_aggregate
    params = inspect.signature(fn).parameters
    takes_normalize = "normalize" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())
    if takes_normalize:
        def agg_fn(g, uploads, weights, semantics):
            return fn(g, uploads, weights, semantics, normalize=normalize)
    elif normalize:
        agg_fn = fn
    else:
        raise TypeError(
            f"aggregator {getattr(aggregator, 'name', fn)!r} does not accept "
            "normalize= but the sampler emits Horvitz-Thompson weights "
            "(normalize=False); extend its fn signature")

    def apply_wire(stacked):
        return roundtrip_stacked(codec, stacked)

    return apply_wire, agg_fn


def _is_plain(sampler, hetero, attack=None) -> bool:
    """True when the round reduces to the original schedule-only body —
    the path kept verbatim so default rounds stay bit-identical.  An
    active attack routes to the generalized body (adversary injection
    needs the full metering path)."""
    return (hetero is None and attack is None
            and (sampler is None or isinstance(sampler, UniformSampler)))


def _active_attack(attack):
    """Normalize the optional attack: a zero-fraction model is no attack."""
    return attack if attack is not None and attack.active else None


def _row_l2(stacked: PyTree) -> jnp.ndarray:
    """Per-client L2 norm over every leaf of a client-stacked pytree."""
    sq = sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                     axis=tuple(range(1, leaf.ndim)))
             for leaf in jax.tree_util.tree_leaves(stacked))
    return jnp.sqrt(sq)


def _finite_rows(stacked: PyTree) -> jnp.ndarray:
    """1.0 for client rows whose every leaf entry is finite, else 0.0 —
    the decode-boundary quarantine gate, shared by both sync engines (the
    async engine applies the same check event-by-event, DESIGN.md §8)."""
    ok = None
    for leaf in jax.tree_util.tree_leaves(stacked):
        leaf_ok = jnp.all(jnp.isfinite(leaf.astype(jnp.float32)),
                          axis=tuple(range(1, leaf.ndim)))
        ok = leaf_ok if ok is None else ok & leaf_ok
    return ok.astype(jnp.float32)


def _zero_rows(stacked: PyTree, keep: jnp.ndarray) -> PyTree:
    """Zero whole client rows where ``keep == 0``.  Quarantined uploads
    must not reach any aggregator even zero-weighted (0 · NaN = NaN); for
    all-finite rows ``jnp.where`` is a bit-exact pass-through, so the
    always-on gate leaves attack-free rounds bit-identical."""
    return jax.tree.map(
        lambda u: jnp.where(
            keep.reshape((-1,) + (1,) * (u.ndim - 1)) > 0,
            u, jnp.zeros_like(u)),
        stacked)


def _attack_payload(attack, wired, adv, mask_key, num_clients,
                    cohort_ids=None):
    """What the server actually decodes: ``wired`` with adversary rows
    transformed.  ``adv`` is the full ``(M,)`` assignment; ``cohort_ids``
    gathers it (and the per-client attack keys) onto cohort rows so both
    engines perturb client i identically.  Returns ``wired`` itself when
    no attack is active — downstream ``is not`` checks stay exact."""
    if attack is None:
        return wired
    keys = None
    if attack.needs_keys:
        keys = attack_keys(mask_key, num_clients)
    if cohort_ids is not None:
        adv = jnp.take(adv, cohort_ids)
        if keys is not None:
            keys = jnp.take(keys, cohort_ids, axis=0)
    return attack.apply_stacked(wired, adv, keys)


@dataclasses.dataclass(frozen=True)
class FederatedConfig:
    """Population-level round configuration: how many clients are
    registered, their shared :class:`repro.core.client.ClientConfig`, and
    whether DGC-style error-feedback residuals accumulate (beyond-paper)."""

    num_clients: int
    client: ClientConfig
    error_feedback: bool = False  # beyond-paper (DGC-style residuals)


def fedavg_aggregate(global_params: PyTree, uploads: PyTree,
                     weights: jnp.ndarray, upload_semantics: str,
                     normalize: bool = True) -> PyTree:
    """Weighted FedAvg over stacked client uploads (leading client axis).

    ``normalize=True`` (default) re-normalizes ``weights`` to sum to 1 —
    Eq. 2's self-normalized mean.  ``normalize=False`` uses the weights as
    given: the Horvitz-Thompson path, where a non-uniform
    :class:`~repro.core.sampling.ClientSampler` has already folded the
    inverse selection probabilities in so the weighted sum is an unbiased
    estimate of the full-population mean.
    """
    if normalize:
        wsum = jnp.maximum(jnp.sum(weights), 1e-12)
        norm_w = weights / wsum
    else:
        norm_w = weights

    def combine(g, u):
        contrib = jnp.tensordot(norm_w, u, axes=(0, 0))
        if upload_semantics == "delta":
            return (g + contrib).astype(g.dtype)
        return contrib.astype(g.dtype)  # "zero": average of masked weights

    return jax.tree.map(combine, global_params, uploads)


def _round_extras(sampler, hetero, cfg):
    """Shared setup for the generalized (non-plain) round bodies: the
    resolved sampler and the static per-client drop-rate vector (or None)."""
    smp = sampler if sampler is not None else UniformSampler()
    drop = None
    if hetero is not None:
        drop = jnp.asarray(hetero.drop_rates(cfg.num_clients), jnp.float32)
    return smp, drop


def _split_round_key(key, with_drop: bool):
    """(sample, mask[, drop]) subkeys; the 2-way split is kept verbatim for
    hetero-free rounds so default rounds stay bit-identical."""
    if not with_drop:
        sample_key, mask_key = jax.random.split(key)
        return sample_key, mask_key, None
    return tuple(jax.random.split(key, 3))


def _apply_dropout(part, weights, drop, drop_key, normalize):
    """Draw upload losses and fold them into participation weights.

    Self-normalized weights just zero the lost rows (FedAvg re-normalizes
    over arrivals); Horvitz-Thompson weights additionally divide by the
    per-client survival probability so unbiasedness is preserved under
    dropout: ``E[arrived_i / (1 - q_i)] = part_i``.
    """
    if drop is None:
        return part, weights
    lost = (jax.random.uniform(drop_key, drop.shape) < drop)
    arrived = part * (1.0 - lost.astype(jnp.float32))
    if normalize:
        return arrived, weights * arrived
    return arrived, weights * arrived / jnp.maximum(1.0 - drop, 1e-6)


def _commit_rows(old: PyTree, new: PyTree, commit: jnp.ndarray) -> PyTree:
    """Per-row state commit: keep ``new[i]`` where ``commit[i] > 0``, else
    the round-entry ``old[i]`` — the same ``where`` every engine's EF
    scatter runs, shared so the drift tree commits identically."""
    return jax.tree.map(
        lambda o, n: jnp.where(
            commit.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o),
        old, new)


def _wire_feedback(new_res: PyTree, uploads: PyTree, wired: PyTree) -> PyTree:
    """EF wire-loss feedback ``r + (u − w)``, identical bits on EVERY
    execution form.

    ``wired`` is pinned through a float->int->float bitcast round-trip
    first: without it the backend may contract a lossy codec's
    dequantisation multiply into the subtraction (an FMA computing
    ``u − q·scale`` in one rounding) in one compiled program but not
    another, and the resulting ±1 ulp wobble breaks the cross-engine
    bit-exactness contract (caught by the store-form body in
    tests/test_equivalence.py).  A bitcast is used rather than
    ``jax.lax.optimization_barrier`` because XLA:CPU deletes barriers
    during optimization; contraction cannot cross an integer bitcast."""
    def pin(w):
        if not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        bits = jnp.dtype(w.dtype).itemsize * 8
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(w, jnp.dtype(f"uint{bits}")),
            w.dtype)

    wired = jax.tree.map(pin, wired)
    return jax.tree.map(lambda r, u, w: r + (u - w), new_res, uploads, wired)


def _wrap_plain(round_impl, uses_drift: bool):
    """Adapt the plain round body ``(params, residuals, drift, batches,
    n_samples, t, key) -> (p, r, d, metrics)`` to its public signature:
    the drift slot appears (after ``residuals``) only when the objective
    carries drift state."""
    if uses_drift:
        return round_impl

    def round_fn(params, residuals, client_batches, n_samples, t, key):
        p, r, _, m = round_impl(params, residuals, None, client_batches,
                                n_samples, t, key)
        return p, r, m

    return round_fn


def _wrap_round(round_impl, uses_drift: bool, adaptive: bool):
    """Adapt the fully-general round body ``(params, residuals, drift,
    norms, batches, n_samples, t, key) -> (p, r, d, n, metrics)`` to the
    public signature for this (uses_drift, adaptive) combination.
    Optional state slots sit between ``residuals`` and the batch args,
    drift first — the convention every engine and the scan carry share."""
    if uses_drift and adaptive:
        return round_impl
    if uses_drift:
        def round_fn(params, residuals, drift, client_batches, n_samples,
                     t, key):
            p, r, d, _, m = round_impl(params, residuals, drift, None,
                                       client_batches, n_samples, t, key)
            return p, r, d, m
    elif adaptive:
        def round_fn(params, residuals, norms, client_batches, n_samples,
                     t, key):
            p, r, _, n, m = round_impl(params, residuals, None, norms,
                                       client_batches, n_samples, t, key)
            return p, r, n, m
    else:
        def round_fn(params, residuals, client_batches, n_samples, t, key):
            p, r, _, _, m = round_impl(params, residuals, None, None,
                                       client_batches, n_samples, t, key)
            return p, r, m
    return round_fn


def make_federated_round(loss_fn: Callable, schedule: SamplingSchedule,
                         cfg: FederatedConfig, *, codec=None, aggregator=None,
                         sampler=None, hetero=None, attack=None):
    """Build the full-population (oracle) round program.

    Returns ``round_fn(params, residuals, client_batches, n_samples, t, key)
    -> (params, residuals, metrics)`` — or, when ``sampler.adaptive``,
    ``round_fn(params, residuals, norms, client_batches, n_samples, t, key)
    -> (params, residuals, norms, metrics)`` with ``norms`` the (M,)
    per-client update-norm tracker the sampler feeds on.  When the
    strategy's :class:`~repro.core.objectives.LocalObjective` carries
    drift state (``cfg.client.objective.uses_drift``, i.e. FedDyn), a
    stacked ``drift`` argument/result is inserted between ``residuals``
    and ``norms`` — the full state convention is
    ``(params, residuals[, drift][, norms], …)``.

    ``client_batches``: pytree with leading (num_clients, num_batches, B, ...)
    axes.  ``n_samples``: (num_clients,) float per-client dataset sizes for
    Eq. 2 weighting.  ``residuals``: stacked error-feedback state (zeros when
    cfg.error_feedback is False).  ``codec`` (an
    ``repro.core.codecs.UploadCodec``) round-trips every client upload
    through its wire format before aggregation; ``aggregator`` (an
    ``repro.core.strategy.Aggregator``) replaces plain weighted FedAvg;
    ``sampler`` (a :class:`repro.core.sampling.ClientSampler`) picks the
    participants and their aggregation weights; ``hetero`` (a
    :class:`repro.core.hetero.HeteroModel`) adds in-round upload dropout
    plus ``part_mask``/``arrived_mask`` metrics for host-side clock
    simulation; ``attack`` (a :class:`repro.core.attacks.AttackModel`)
    perturbs the adversary rows of the decoded payload before aggregation.

    Both bodies gate the decoded payload through the non-finite quarantine
    (``metrics["quarantined"]``): a NaN/Inf upload is zero-weighted and
    zeroed out instead of poisoning Θ, matching the async engine's gate.
    """
    attack = _active_attack(attack)
    uses_drift = cfg.client.objective.uses_drift
    if _is_plain(sampler, hetero, attack):
        apply_wire, agg_fn = _resolve_policies(codec, aggregator)

        def plain_impl(params, residuals, drift, client_batches, n_samples,
                       t, key):
            with jax.named_scope("fl.local_update"):
                sample_key, mask_key = jax.random.split(key)
                part = participation_mask(sample_key, schedule, t,
                                          cfg.num_clients)
                mask_keys = jax.random.split(mask_key, cfg.num_clients)

                (uploads, new_residuals, new_drift,
                 losses) = stacked_client_update(
                    loss_fn, params, client_batches, mask_keys, cfg.client,
                    residuals, cfg.error_feedback, drift)

            wired = apply_wire(uploads)
            with jax.named_scope("fl.aggregate"):
                finite = _finite_rows(wired)
                weights = part * n_samples * finite
                new_params = agg_fn(params, _zero_rows(wired, finite), weights,
                                    cfg.client.upload)
                if cfg.error_feedback:
                    if wired is not uploads:
                        # Wire loss (int8 quantisation, slot truncation) is
                        # real masked-out mass: feed it back like any other
                        # residual so error feedback compensates for the codec
                        # too.  Exact no-op for bit-exact wires (u - w == 0).
                        new_residuals = _wire_feedback(new_residuals, uploads,
                                                       wired)
                    # Non-participants did not really run this round: keep
                    # their old residual; participants reset to the post-mask
                    # remainder.  Quarantined rows count as non-participants
                    # (their whole update was discarded at the server).
                    new_residuals = _commit_rows(residuals, new_residuals,
                                                 part * finite)
                else:
                    new_residuals = residuals

                if uses_drift:
                    # Drift advances under the same gate as the residuals (the
                    # upload applied), but independent of error_feedback: h_k
                    # tracks the honest local trajectory, not the wire.
                    new_drift = _commit_rows(drift, new_drift, part * finite)
                else:
                    new_drift = drift

                metrics = {
                    "mean_loss": jnp.sum(losses * part)
                    / jnp.maximum(jnp.sum(part), 1.0),
                    "num_sampled": jnp.sum(part),
                    "quarantined": jnp.sum(part * (1.0 - finite)),
                }
            return new_params, new_residuals, new_drift, metrics

        return _wrap_plain(plain_impl, uses_drift)

    smp, drop = _round_extras(sampler, hetero, cfg)
    apply_wire, agg_fn = _resolve_policies(codec, aggregator, smp.normalize)
    adv = None
    if attack is not None:
        adv = jnp.asarray(attack.adversary_mask(cfg.num_clients),
                          jnp.float32)

    def round_impl(params, residuals, drift, norms, client_batches,
                   n_samples, t, key):
        M = cfg.num_clients
        with jax.named_scope("fl.local_update"):
            sample_key, mask_key, drop_key = _split_round_key(
                key, drop is not None)
            part, weights = smp.select(sample_key, schedule, t, M, n_samples,
                                       norms)
            mask_keys = jax.random.split(mask_key, M)

            uploads, new_residuals, new_drift, losses = stacked_client_update(
                loss_fn, params, client_batches, mask_keys, cfg.client,
                residuals, cfg.error_feedback, drift)

        wired = apply_wire(uploads)
        # What the server decodes: adversary rows perturbed, then the
        # non-finite quarantine gate.  EF wire-loss feedback below stays on
        # the HONEST (uploads, wired) pair — a client's residual reflects
        # what IT failed to ship, not what an attacker forged in its name.
        payload = _attack_payload(attack, wired, adv, mask_key, M)
        with jax.named_scope("fl.aggregate"):
            finite = _finite_rows(payload)
            arrived, weights = _apply_dropout(part, weights, drop, drop_key,
                                              smp.normalize)
            weights = weights * finite
            new_params = agg_fn(params, _zero_rows(payload, finite), weights,
                                cfg.client.upload)
            if cfg.error_feedback:
                if wired is not uploads:
                    new_residuals = _wire_feedback(new_residuals, uploads,
                                                   wired)
                # Residuals advance only for clients whose upload ARRIVED (and
                # survived quarantine): a dropped upload discards the whole
                # local update, so its residual must stay consistent with the
                # global model the client re-downloads next round.
                new_residuals = _commit_rows(residuals, new_residuals,
                                             arrived * finite)
            else:
                new_residuals = residuals

            if uses_drift:
                # Same arrival gate as the residuals; independent of
                # error_feedback (drift tracks the honest local trajectory).
                new_drift = _commit_rows(drift, new_drift, arrived * finite)
            else:
                new_drift = drift

            new_norms = norms
            if smp.adaptive:
                # The tracker observes what the server saw — attacked rows
                # feed their forged norms in, exactly the signal a
                # norm-adaptive sampler would really receive under attack.
                obs = _row_l2(payload)
                new_norms = jnp.where(
                    arrived * finite > 0,
                    (1.0 - smp.ema) * norms + smp.ema * obs, norms)

            # An empty round (the threshold sampler's random count can be 0) is
            # a no-op for the params; report NaN, not a fabricated 0.0 loss.
            n_part = jnp.sum(part)
            metrics = {
                "mean_loss": jnp.where(
                    n_part > 0,
                    jnp.sum(losses * part) / jnp.maximum(n_part, 1.0),
                    jnp.nan),
                "num_sampled": n_part,
                "quarantined": jnp.sum(arrived * (1.0 - finite)),
            }
            if attack is not None:
                metrics["num_adversarial"] = jnp.sum(part * adv)
            if drop is not None:
                metrics["part_mask"] = part
                metrics["arrived_mask"] = arrived
                metrics["num_arrived"] = jnp.sum(arrived)
        return new_params, new_residuals, new_drift, new_norms, metrics

    return _wrap_round(round_impl, uses_drift, smp.adaptive)


# ---------------------------------------------------------------------------
# Cohort execution engine (DESIGN.md §3.5)
# ---------------------------------------------------------------------------
# The oracle above runs EVERY registered client and multiplies
# non-participants by zero — per-round compute/memory is flat in c(t).  The
# cohort engine materializes only a padded cohort buffer of static size
# ``cohort_size`` (a SamplingSchedule.bucket_ladder entry >= m_t): gather the
# m_t participants' batch shards + error-feedback residuals into the buffer,
# vmap client_update over the cohort axis only, and scatter residuals back
# under the participation mask.  Padding slots (cohort rank >= m_t) execute
# but are masked out of the aggregation — exactly the oracle's zero-weight
# treatment, restricted to at most bucket-m_t clients instead of M-m_t.
#
# Equivalence with the oracle is by construction:
#   * the participant SET is identical — both rank the same uniform draw
#     from ``sample_key`` and keep ranks < m_t;
#   * per-client mask keys are row i of split(mask_key, M) in both paths;
#   * cohort ids are sorted ascending so the weighted reduction visits
#     participants in the same client-id order as the oracle (its extra
#     terms are exact zeros).


def cohort_select(sample_key: jax.Array, schedule: SamplingSchedule, t,
                  num_clients: int, cohort_size: int):
    """Pick the round's cohort: ``(cohort_ids, valid)`` with ids sorted
    ascending and ``valid[i] = 1`` iff cohort member i is a true participant
    (its global rank < m_t).  Identical participant set to
    :func:`repro.core.sampling.participation_mask` under the same key."""
    m = schedule.num_clients(t, num_clients)
    scores = jax.random.uniform(sample_key, (num_clients,))
    order = jnp.argsort(scores)                  # ids by ascending score
    ranks = jnp.argsort(order)                   # rank of each client id
    cohort_ids = jnp.sort(order[:cohort_size])   # participant superset
    valid = (jnp.take(ranks, cohort_ids) < m).astype(jnp.float32)
    return cohort_ids, valid


def make_cohort_compute(loss_fn: Callable, schedule: SamplingSchedule,
                        cfg: FederatedConfig, cohort_size: int, *,
                        codec=None, sampler=None, attack=None):
    """The round's *client-side sweep*, shared between execution engines:
    selection → cohort gather → local updates → wire round-trip — and
    nothing after it (no dropout draw, no aggregation, no state commit).

    The sync cohort engine (``make_cohort_round``) runs this then applies
    its barrier aggregation in the same jitted program; the async buffered
    engine (``repro.core.async_engine``) runs it as the round's *dispatch*
    phase and applies the uploads event-by-event as they arrive.  Both see
    the identical uploads because the whole sweep is a pure function of
    ``(params, residuals, norms, t, sample_key, mask_key)``.

    Returns ``compute(params, residuals, drift, norms, client_batches,
    n_samples, t, sample_key, mask_key) -> dict`` with keys ``part`` /
    ``weights`` (full ``(M,)`` selection mask and pre-dropout aggregation
    weights), ``cohort_ids`` (sorted ascending, padded with the lowest-id
    non-participants), ``cohort_res`` / ``cohort_drift`` (round-entry
    state rows, gathered), ``uploads`` / ``wired`` (pre-/post-wire stacked
    uploads), ``attacked`` (the payload the server decodes: ``wired`` with
    adversary rows perturbed — the same object when no attack is active),
    ``new_res`` / ``new_drift`` (post-round state candidates) and
    ``losses`` — everything a barrier or a buffer needs to finish the
    round.  Pass ``norms=None`` for non-adaptive samplers and
    ``drift=None`` unless ``cfg.client.objective.uses_drift``.
    """
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    smp = sampler if sampler is not None else UniformSampler()
    attack = _active_attack(attack)
    adv = None
    if attack is not None:
        adv = jnp.asarray(attack.adversary_mask(cfg.num_clients),
                          jnp.float32)

    def compute(params, residuals, drift, norms, client_batches, n_samples,
                t, sample_key, mask_key):
        with jax.named_scope("fl.local_update"):
            M = cfg.num_clients
            # Selection runs on the full (M,) arrays — identical ops to the
            # oracle — then the cohort buffer gathers the sampler's ids.
            part, weights = smp.select(sample_key, schedule, t, M, n_samples,
                                       norms)
            ids = jnp.arange(M, dtype=jnp.int32)
            order = jnp.argsort(jnp.where(part > 0, ids, ids + M))
            cohort_ids = jnp.sort(order[:cohort_size])

            def gather(x):
                return jnp.take(x, cohort_ids, axis=0)

            cohort_batches = jax.tree.map(gather, client_batches)
            cohort_res = jax.tree.map(gather, residuals)
            cohort_drift = jax.tree.map(gather, drift)  # None stays None
            mask_keys = jnp.take(
                jax.random.split(mask_key, M), cohort_ids, axis=0)

            uploads, new_res, new_drift, losses = stacked_client_update(
                loss_fn, params, cohort_batches, mask_keys, cfg.client,
                cohort_res, cfg.error_feedback, cohort_drift)

        wired = roundtrip_stacked(codec, uploads)
        attacked = _attack_payload(attack, wired, adv, mask_key, M,
                                   cohort_ids=cohort_ids)
        return {
            "part": part,
            "weights": weights,
            "cohort_ids": cohort_ids,
            "cohort_res": cohort_res,
            "cohort_drift": cohort_drift,
            "uploads": uploads,
            "new_res": new_res,
            "new_drift": new_drift,
            "losses": losses,
            "wired": wired,
            "attacked": attacked,
        }

    return compute


def make_cohort_round(loss_fn: Callable, schedule: SamplingSchedule,
                      cfg: FederatedConfig, cohort_size: int, *,
                      codec=None, aggregator=None, sampler=None, hetero=None,
                      attack=None):
    """Cohort-engine form of ``make_federated_round``: same signature(s) and
    math, but client_update runs over ``cohort_size`` (static) clients
    instead of ``cfg.num_clients``.

    Requires ``cohort_size`` to upper-bound the sampler's participant count
    for every round it is dispatched to — the server guarantees this via
    ``ClientSampler.cohort_bucket`` (``SamplingSchedule.bucket_for`` for
    the default uniform sampler).  Under a non-uniform sampler the cohort
    gather is keyed by the sampler's ids: the selection math runs on the
    full (M,)-shaped arrays exactly as in the oracle, and the cohort
    buffer gathers the ``part > 0`` ids (sorted ascending, padded with the
    lowest-id non-participants) so the weighted reductions see the same
    nonzero terms in the same order — bit-exact vs the oracle.
    """
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    attack = _active_attack(attack)
    uses_drift = cfg.client.objective.uses_drift

    if _is_plain(sampler, hetero, attack):
        apply_wire, agg_fn = _resolve_policies(codec, aggregator)

        def plain_impl(params, residuals, drift, client_batches, n_samples,
                       t, key):
            with jax.named_scope("fl.local_update"):
                sample_key, mask_key = jax.random.split(key)
                cohort_ids, valid = cohort_select(
                    sample_key, schedule, t, cfg.num_clients, cohort_size)

                def gather(x):
                    return jnp.take(x, cohort_ids, axis=0)

                cohort_batches = jax.tree.map(gather, client_batches)
                cohort_res = jax.tree.map(gather, residuals)
                cohort_drift = jax.tree.map(gather, drift)
                mask_keys = jnp.take(
                    jax.random.split(mask_key, cfg.num_clients), cohort_ids,
                    axis=0)

                uploads, new_res, new_drift, losses = stacked_client_update(
                    loss_fn, params, cohort_batches, mask_keys, cfg.client,
                    cohort_res, cfg.error_feedback, cohort_drift)

            wired = apply_wire(uploads)
            with jax.named_scope("fl.aggregate"):
                finite = _finite_rows(wired)
                weights = valid * jnp.take(n_samples, cohort_ids) * finite
                new_params = agg_fn(params, _zero_rows(wired, finite), weights,
                                    cfg.client.upload)

                def scatter_back(full_old, rows, cohort_old, commit):
                    def scatter(old, new, old_cohort):
                        vm = commit.reshape((-1,) + (1,) * (new.ndim - 1))
                        kept = jnp.where(vm > 0, new, old_cohort)
                        return old.at[cohort_ids].set(kept)

                    return jax.tree.map(scatter, full_old, rows, cohort_old)

                if cfg.error_feedback:
                    if wired is not uploads:
                        # Same wire-loss feedback as the oracle round
                        # (bit-exact equivalence holds: both engines adjust
                        # identically).
                        new_res = _wire_feedback(new_res, uploads, wired)
                    new_residuals = scatter_back(residuals, new_res,
                                                 cohort_res, valid * finite)
                else:
                    new_residuals = residuals

                if uses_drift:
                    new_drift = scatter_back(drift, new_drift, cohort_drift,
                                             valid * finite)
                else:
                    new_drift = drift

                metrics = {
                    "mean_loss": jnp.sum(losses * valid)
                    / jnp.maximum(jnp.sum(valid), 1.0),
                    "num_sampled": jnp.sum(valid),
                    "quarantined": jnp.sum(valid * (1.0 - finite)),
                }
            return new_params, new_residuals, new_drift, metrics

        return _wrap_plain(plain_impl, uses_drift)

    smp, drop = _round_extras(sampler, hetero, cfg)
    _, agg_fn = _resolve_policies(codec, aggregator, smp.normalize)
    compute = make_cohort_compute(loss_fn, schedule, cfg, cohort_size,
                                  codec=codec, sampler=sampler, attack=attack)
    adv = None
    if attack is not None:
        adv = jnp.asarray(attack.adversary_mask(cfg.num_clients),
                          jnp.float32)

    def round_impl(params, residuals, drift, norms, client_batches,
                   n_samples, t, key):
        with jax.named_scope("fl.local_update"):
            sample_key, mask_key, drop_key = _split_round_key(
                key, drop is not None)
        # The client-side sweep (selection → gather → updates → wire →
        # adversary injection) is the engine-shared compute; everything
        # below is this engine's barrier: dropout draw, quarantine gate,
        # one-shot aggregation, state commit.
        c = compute(params, residuals, drift, norms, client_batches,
                    n_samples, t, sample_key, mask_key)
        part, cohort_ids = c["part"], c["cohort_ids"]
        uploads, new_res, wired = c["uploads"], c["new_res"], c["wired"]
        losses, payload = c["losses"], c["attacked"]
        with jax.named_scope("fl.aggregate"):
            finite = _finite_rows(payload)
            arrived, weights = _apply_dropout(part, c["weights"], drop,
                                              drop_key, smp.normalize)

            def gather(x):
                return jnp.take(x, cohort_ids, axis=0)

            valid = gather(part)
            arr_c = gather(arrived)
            w_c = gather(weights) * finite
            new_params = agg_fn(params, _zero_rows(payload, finite), w_c,
                                cfg.client.upload)

            def scatter_back(full_old, rows, cohort_old, commit):
                def scatter(old, new, old_cohort):
                    am = commit.reshape((-1,) + (1,) * (new.ndim - 1))
                    kept = jnp.where(am > 0, new, old_cohort)
                    return old.at[cohort_ids].set(kept)

                return jax.tree.map(scatter, full_old, rows, cohort_old)

            if cfg.error_feedback:
                # EF feedback stays on the HONEST (uploads, wired) pair — see
                # the oracle body.
                if wired is not uploads:
                    new_res = _wire_feedback(new_res, uploads, wired)
                new_residuals = scatter_back(residuals, new_res,
                                             c["cohort_res"], arr_c * finite)
            else:
                new_residuals = residuals

            if uses_drift:
                new_drift = scatter_back(drift, c["new_drift"],
                                         c["cohort_drift"], arr_c * finite)
            else:
                new_drift = drift

            new_norms = norms
            if smp.adaptive:
                obs = _row_l2(payload)
                old_c = gather(norms)
                upd = jnp.where(arr_c * finite > 0,
                                (1.0 - smp.ema) * old_c + smp.ema * obs, old_c)
                new_norms = norms.at[cohort_ids].set(upd)

            # Same empty-round convention as the oracle body: NaN, not 0.0.
            n_part = jnp.sum(part)
            metrics = {
                "mean_loss": jnp.where(
                    n_part > 0,
                    jnp.sum(losses * valid) / jnp.maximum(jnp.sum(valid), 1.0),
                    jnp.nan),
                "num_sampled": n_part,
                "quarantined": jnp.sum(arr_c * (1.0 - finite)),
            }
            if attack is not None:
                metrics["num_adversarial"] = jnp.sum(part * adv)
            if drop is not None:
                metrics["part_mask"] = part
                metrics["arrived_mask"] = arrived
                metrics["num_arrived"] = jnp.sum(arrived)
        return new_params, new_residuals, new_drift, new_norms, metrics

    return _wrap_round(round_impl, uses_drift, smp.adaptive)


def make_cohort_scan(loss_fn: Callable, schedule: SamplingSchedule,
                     cfg: FederatedConfig, cohort_size: int, *,
                     codec=None, aggregator=None, sampler=None, hetero=None,
                     attack=None):
    """lax.scan-over-rounds fast path: one dispatch for a whole segment of
    rounds that share a cohort bucket.

    Returns ``scan_fn(params, residuals, client_batches, n_samples, ts,
    keys) -> (params, residuals, metrics)`` where ``ts``/``keys`` carry a
    leading segment-length axis and ``metrics`` leaves are stacked per
    round.  Optional state (FedDyn ``drift``, then the adaptive samplers'
    ``norms``) extends the argument/result lists after ``residuals`` in
    the engine-wide ``(params, residuals[, drift][, norms], …)``
    convention, threaded through the scan carry.  Bit-identical to calling
    the single-round function in a Python loop (same round body, scan just
    removes per-round dispatch)."""
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    kw = dict(codec=codec, aggregator=aggregator, sampler=sampler,
              hetero=hetero, attack=attack)
    if cohort_size == cfg.num_clients:
        round_fn = make_federated_round(loss_fn, schedule, cfg, **kw)
    else:
        round_fn = make_cohort_round(loss_fn, schedule, cfg, cohort_size,
                                     **kw)

    adaptive = sampler is not None and sampler.adaptive
    uses_drift = cfg.client.objective.uses_drift
    n_state = 2 + int(uses_drift) + int(adaptive)

    def scan_fn(*args):
        state = tuple(args[:n_state])
        client_batches, n_samples, ts, keys = args[n_state:]

        def body(carry, tk):
            t, k = tk
            out = round_fn(*carry, client_batches, n_samples, t, k)
            return tuple(out[:-1]), out[-1]

        state, metrics = jax.lax.scan(body, state, (ts, keys))
        return (*state, metrics)

    return scan_fn


# ---------------------------------------------------------------------------
# Store-form round (DESIGN.md §11)
# ---------------------------------------------------------------------------
# The two engines above close over the full (M, …) residual arrays: gather
# and scatter happen INSIDE the round program, so the dense stack must exist
# as a program input.  The store form splits the round at exactly that
# boundary so residual ownership can move into a
# ``repro.core.client_store.ClientStateStore`` (dense oracle or sharded slot
# pool) and the program only ever sees cohort-shaped rows:
#
#     select(norms, n_samples, t, sample_key)          [jit, (M,) arrays]
#         -> part, weights, cohort_ids
#     store.gather(cohort_ids)                          [host boundary]
#         -> cohort_res
#     body(params, cohort_res, cohort_batches, cohort_ids,
#          part, weights, norms, mask_key, drop_key)    [jit, cohort-shaped]
#         -> new_params, new_rows, commit, norm_upd, metrics
#     store.scatter(cohort_ids, new_rows, commit)       [host boundary]
#     store.update_norms(cohort_ids, norm_upd)
#
# Equivalence with the in-program engines is by the same construction
# argument as cohort-vs-oracle: the participant set, per-client mask keys
# and all per-row math are identical; cohort ids are sorted ascending so
# weighted reductions visit participants in client-id order (padding rows
# contribute exact zeros); and the store's commit-masked scatter is the very
# ``where(commit, new, old) → at[ids].set`` the in-program scatter ran.
# Padding rows never commit, so a sharded gather returning zeros for a
# client the window forgot can only differ from dense on rows whose output
# is masked out of every reduction and never written back.


def make_store_selection(schedule: SamplingSchedule, cfg: FederatedConfig,
                         cohort_size: int, *, sampler=None):
    """The round's *selection head*, jittable in isolation.

    Returns ``select(norms, n_samples, t, sample_key) -> (part, weights,
    cohort_ids)``: the participation draw on the full ``(M,)`` arrays
    (identical ops to the in-program selection of
    :func:`make_cohort_compute`) plus the sorted cohort-id buffer —
    everything the host needs to gather residual rows through a
    :class:`~repro.core.client_store.ClientStateStore` before dispatching
    the cohort-shaped body.  Pass ``norms=None`` for non-adaptive samplers.
    """
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    smp = sampler if sampler is not None else UniformSampler()

    def select(norms, n_samples, t, sample_key):
        with jax.named_scope("fl.local_update"):
            M = cfg.num_clients
            part, weights = smp.select(sample_key, schedule, t, M, n_samples,
                                       norms)
            ids = jnp.arange(M, dtype=jnp.int32)
            order = jnp.argsort(jnp.where(part > 0, ids, ids + M))
            cohort_ids = jnp.sort(order[:cohort_size])
        return part, weights, cohort_ids

    return select


def make_store_compute(loss_fn: Callable, cfg: FederatedConfig, *,
                       codec=None, attack=None):
    """Cohort-shaped client sweep over PRE-GATHERED residual rows.

    The store-form sibling of :func:`make_cohort_compute`: selection and
    the residual gather already happened outside the program, so this is
    the pure sweep — local updates → wire round-trip → adversary
    injection.  Returns ``compute(params, cohort_res, cohort_batches,
    cohort_ids, mask_key, cohort_drift=None) -> dict`` with keys
    ``uploads`` / ``wired`` / ``attacked`` / ``new_res`` / ``new_drift`` /
    ``losses`` (same meanings as :func:`make_cohort_compute`'s;
    ``cohort_drift`` carries the pre-gathered FedDyn drift rows when the
    objective uses them).  Per-client mask keys are row i of
    ``split(mask_key, M)`` exactly as in every other engine, so client i's
    masking draw does not depend on which execution form ran it.
    """
    attack = _active_attack(attack)
    adv = None
    if attack is not None:
        adv = jnp.asarray(attack.adversary_mask(cfg.num_clients),
                          jnp.float32)

    def compute(params, cohort_res, cohort_batches, cohort_ids, mask_key,
                cohort_drift=None):
        with jax.named_scope("fl.local_update"):
            M = cfg.num_clients
            mask_keys = jnp.take(
                jax.random.split(mask_key, M), cohort_ids, axis=0)
            uploads, new_res, new_drift, losses = stacked_client_update(
                loss_fn, params, cohort_batches, mask_keys, cfg.client,
                cohort_res, cfg.error_feedback, cohort_drift)
        wired = roundtrip_stacked(codec, uploads)
        attacked = _attack_payload(attack, wired, adv, mask_key, M,
                                   cohort_ids=cohort_ids)
        return {
            "uploads": uploads,
            "new_res": new_res,
            "new_drift": new_drift,
            "losses": losses,
            "wired": wired,
            "attacked": attacked,
        }

    return compute


@dataclasses.dataclass(frozen=True)
class StoreRound:
    """The store-form round program, split at the store boundary.

    ``select`` and ``body`` are independently jittable; the driver
    (``FederatedServer._run_store``) moves residual rows between them
    through a :class:`~repro.core.client_store.ClientStateStore`.  The
    flags tell the driver which optional state the pieces consume."""

    select: Callable   # (norms, n_samples, t, sample_key) -> (part, w, ids)
    body: Callable     # cohort-shaped barrier; see make_store_round
    adaptive: bool     # body consumes/updates the (M,) norm EMA
    with_drop: bool    # round key splits 3 ways (hetero dropout draw)
    error_feedback: bool  # residual rows need scattering back
    uses_drift: bool = False  # body consumes/emits FedDyn drift rows


def make_store_round(loss_fn: Callable, schedule: SamplingSchedule,
                     cfg: FederatedConfig, cohort_size: int, *,
                     codec=None, aggregator=None, sampler=None, hetero=None,
                     attack=None) -> StoreRound:
    """Store-form sibling of :func:`make_cohort_round`.

    Same math as the generalized cohort body, but state gather/scatter
    are OUTSIDE the program: ``body(params, cohort_res, cohort_drift,
    cohort_batches, cohort_ids, part, weights, norms, mask_key, drop_key)
    -> (new_params, new_rows, drift_rows, commit, norm_upd, metrics)``
    where ``new_rows`` are the finalized post-round residual candidates
    (wire-loss feedback already folded in), ``drift_rows`` the post-round
    FedDyn drift candidates (None unless the objective uses drift),
    ``commit`` is the per-cohort-row "this upload applied" mask
    (``arrived × finite`` — ALWAYS computed; the driver gates the residual
    scatter on ``error_feedback`` and the drift scatter on ``uses_drift``),
    and ``norm_upd`` is the cohort's updated norm-EMA rows (None for
    non-adaptive samplers; rows with no arrival carry the old value, so
    setting them back is a no-op).

    Unlike the in-program engines there is no separate plain path: the
    generalized body IS bit-exact for plain rounds too — the uniform
    sampler's selection draw matches ``participation_mask``, and the only
    difference from ``cohort_select``'s buffer is WHICH non-participants
    pad the cohort, rows that contribute exact zeros to every reduction
    and never commit.
    """
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    attack = _active_attack(attack)
    smp, drop = _round_extras(sampler, hetero, cfg)
    _, agg_fn = _resolve_policies(codec, aggregator, smp.normalize)
    compute = make_store_compute(loss_fn, cfg, codec=codec, attack=attack)
    select = make_store_selection(schedule, cfg, cohort_size, sampler=sampler)
    adv = None
    if attack is not None:
        adv = jnp.asarray(attack.adversary_mask(cfg.num_clients),
                          jnp.float32)

    def body(params, cohort_res, cohort_drift, cohort_batches, cohort_ids,
             part, weights, norms, mask_key, drop_key):
        c = compute(params, cohort_res, cohort_batches, cohort_ids, mask_key,
                    cohort_drift)
        uploads, new_res, wired = c["uploads"], c["new_res"], c["wired"]
        losses, payload = c["losses"], c["attacked"]
        drift_rows = c["new_drift"]
        with jax.named_scope("fl.aggregate"):
            finite = _finite_rows(payload)
            arrived, weights = _apply_dropout(part, weights, drop, drop_key,
                                              smp.normalize)

            def gather(x):
                return jnp.take(x, cohort_ids, axis=0)

            valid = gather(part)
            arr_c = gather(arrived)
            w_c = gather(weights) * finite
            new_params = agg_fn(params, _zero_rows(payload, finite), w_c,
                                cfg.client.upload)
            commit = arr_c * finite
            if cfg.error_feedback:
                # EF feedback on the HONEST (uploads, wired) pair, exactly as
                # in the in-program engines.
                if wired is not uploads:
                    new_res = _wire_feedback(new_res, uploads, wired)

            norm_upd = None
            if smp.adaptive:
                obs = _row_l2(payload)
                old_c = gather(norms)
                norm_upd = jnp.where(arr_c * finite > 0,
                                     (1.0 - smp.ema) * old_c + smp.ema * obs,
                                     old_c)

            n_part = jnp.sum(part)
            metrics = {
                "mean_loss": jnp.where(
                    n_part > 0,
                    jnp.sum(losses * valid) / jnp.maximum(jnp.sum(valid), 1.0),
                    jnp.nan),
                "num_sampled": n_part,
                "quarantined": jnp.sum(arr_c * (1.0 - finite)),
            }
            if attack is not None:
                metrics["num_adversarial"] = jnp.sum(part * adv)
            if drop is not None:
                metrics["part_mask"] = part
                metrics["arrived_mask"] = arrived
                metrics["num_arrived"] = jnp.sum(arrived)
        return new_params, new_res, drift_rows, commit, norm_upd, metrics

    return StoreRound(select=select, body=body, adaptive=smp.adaptive,
                      with_drop=drop is not None,
                      error_feedback=cfg.error_feedback,
                      uses_drift=cfg.client.objective.uses_drift)
