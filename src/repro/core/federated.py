"""The federated round: one body in three pieces, composed into every form.

Paper Alg. 1/3 server loop body + Alg. 2/4 client bodies, written once:

* **select** (:func:`make_store_selection`) — the sampler's participation
  mask and aggregation weights on the full ``(M,)`` arrays, plus the
  cohort-id buffer: the ``part > 0`` ids sorted ascending, padded with the
  lowest-id non-participants;
* **sweep** (:func:`make_store_compute`) — every cohort row's local update,
  its wire round-trip and the adversary payload;
* **barrier** (:func:`_make_barrier`) — quarantine gate, upload dropout,
  weighted FedAvg (Eq. 2): Θ_{t+1} = Θ_t + Σ_i w_i · upload_i with
  w_i = mask_i·n_i / Σ mask_j·n_j, error-feedback wire feedback, the
  per-row commit mask ``arrived × finite``, norm-EMA rows and the metrics.

Note on Eq. 1/2: the paper writes an extra 1/m in front of Σ (n_i/n)Θ^i; since
the n_i/n weights already sum to 1 over the selected set, the extra 1/m would
shrink the model m-fold.  We take Σ (n_i/n)Θ^i, which matches FedAvg
(McMahan et al.) and the paper's cited behaviour.

The forms are compositions of the pieces:

* **cohort engine** (``make_cohort_round`` / ``make_cohort_scan``): select,
  gather the cohort's rows into a bucketed padded buffer, sweep, barrier,
  scatter the committed rows back (DESIGN.md §3.5) — per-round work decays
  with c(t);
* **oracle** (``make_federated_round``): the same composition with the
  cohort = all M clients, so the gathers and scatters drop out statically
  and non-participants are zero-weighted;
* **store form** (``make_store_round``): select is one program, sweep +
  barrier the other; state rows move between them through a
  ``ClientStateStore`` (DESIGN.md §11);
* **async dispatch** (``make_cohort_compute``): select, gather, sweep; the
  async engine applies the uploads as they arrive (DESIGN.md §8).

Equivalence across forms is by construction: the participant set and the
weights come from one select; per-client mask keys are row i of
``split(mask_key, M)`` whichever rows run; cohort ids are sorted ascending,
so weighted reductions visit participants in client-id order (padding rows
and the oracle's non-participants add exact zeros); and every state commit
is the same ``where(commit, new, old)``.

Every builder takes two further scenario axes (DESIGN.md §5):

* ``sampler`` — a :class:`repro.core.sampling.ClientSampler` picking WHICH
  m_t clients and the aggregation weights that keep the weighted mean
  unbiased under non-uniform selection.  Adaptive samplers (importance /
  threshold) consume and emit a per-client norm-tracker vector, so the
  round signature gains a ``norms`` state argument/result.
* ``hetero`` — a :class:`repro.core.hetero.HeteroModel`; its per-client
  drop rates are drawn INSIDE the round (a dropped upload is zero-weighted
  and, under error feedback, leaves that client's residual untouched), so
  every form agrees bit-exactly on which uploads count.

The pod (shard_map) form of the same round lives in
``repro.launch.fedtrain`` — identical math, collectives instead of vmap.

Every piece names its layers with ``jax.named_scope``, so each op's
``op_name`` (and a device trace) says which layer it belongs to:
``fl.local_update`` (selection, row gathers, the client updates),
``fl.mask`` (inside it, ``client.client_update``), ``fl.encode`` /
``fl.decode`` (``UploadCodec.roundtrip``) and ``fl.aggregate`` (the
barrier and the state scatter).  Scopes change metadata only.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.attacks import attack_keys
from repro.core.client import ClientConfig, stacked_client_update
from repro.core.codecs import roundtrip_stacked
from repro.core.sampling import SamplingSchedule, UniformSampler

PyTree = Any

__all__ = ["FederatedConfig", "make_federated_round", "make_cohort_round",
           "make_cohort_scan", "make_cohort_compute", "cohort_select",
           "fedavg_aggregate", "make_store_selection", "make_store_compute",
           "make_store_round", "StoreRound", "aot_compile"]


def _resolve_aggregator(aggregator, normalize: bool = True):
    """The aggregation call ``agg_fn(params, uploads, weights, semantics)``
    of the optional ``aggregator`` (plain fedavg when unset).

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
    return agg_fn


def _active_attack(attack):
    """Normalize the optional attack: a zero-fraction model is no attack."""
    return attack if attack is not None and attack.active else None


def _adversaries(attack, num_clients: int) -> jnp.ndarray:
    """The attack's static ``(M,)`` 0/1 adversary assignment."""
    return jnp.asarray(attack.adversary_mask(num_clients), jnp.float32)


def _rows(tree: PyTree, ids) -> PyTree:
    """Client rows ``ids`` of a client-stacked pytree (``None`` leaves
    stay ``None``).  ``ids=None`` is the whole population: no gather."""
    if ids is None:
        return tree
    return jax.tree.map(lambda x: jnp.take(x, ids, axis=0), tree)


def _row_l2(stacked: PyTree) -> jnp.ndarray:
    """Per-client L2 norm over every leaf of a client-stacked pytree."""
    sq = sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                     axis=tuple(range(1, leaf.ndim)))
             for leaf in jax.tree_util.tree_leaves(stacked))
    return jnp.sqrt(sq)


def _finite_rows(stacked: PyTree) -> jnp.ndarray:
    """1.0 for client rows whose every leaf entry is finite, else 0.0 —
    the decode-boundary quarantine gate of the barrier (the async engine
    applies the same check event-by-event, DESIGN.md §8)."""
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


def _attack_payload(attack, wired, mask_key, num_clients, cohort_ids):
    """What the server actually decodes: ``wired`` with adversary rows
    transformed.  The ``(M,)`` assignment and per-client attack keys are
    gathered onto the cohort rows (``cohort_ids=None``: all M), so every
    form perturbs client i identically.  Returns ``wired`` itself when no
    attack is active — downstream ``is not`` checks stay exact."""
    if attack is None:
        return wired
    keys = attack_keys(mask_key, num_clients) if attack.needs_keys else None
    adv, keys = _rows((_adversaries(attack, num_clients), keys), cohort_ids)
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
    the round-entry ``old[i]`` — the same ``where`` every engine's state
    commit runs, shared so every state tree commits identically."""
    return jax.tree.map(
        lambda o, n: jnp.where(
            commit.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o),
        old, new)


def _scatter_rows(full: PyTree, cohort_old: PyTree, rows: PyTree,
                  commit: jnp.ndarray, ids) -> PyTree:
    """Commit cohort ``rows`` into the full ``(M, …)`` state: the
    commit-masked ``where`` on the cohort, then ``at[ids].set`` (no
    scatter when ``ids=None``, the whole population)."""
    kept = _commit_rows(cohort_old, rows, commit)
    if ids is None:
        return kept
    return jax.tree.map(lambda o, k: o.at[ids].set(k), full, kept)


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


def aot_compile(cache: dict, tag: str, bucket: int, build: Callable, args,
                seg_len: int = 1):
    """AOT-compile ``build()`` for ``args`` once, cached in ``cache``.

    The key is ``(tag, bucket, seg_len, input avals)``, so a later call
    with differently-shaped data recompiles instead of hitting a stale
    executable (AOT calls don't retrace the way plain jit does).  Returns
    ``(executable, compile_s)`` with ``compile_s = 0.0`` on a hit.  A miss
    builds the program and compiles it inside an ``fl.compile`` host span.
    """
    avals = tuple((tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(args))
    cache_key = (tag, bucket, seg_len, avals)
    hit = cache.get(cache_key)
    if hit is not None:
        return hit, 0.0
    with jax.profiler.TraceAnnotation("fl.compile", bucket=bucket):
        fn = build()
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
    cache[cache_key] = compiled
    return compiled, compile_s


# ---------------------------------------------------------------------------
# The three pieces
# ---------------------------------------------------------------------------
def make_store_selection(schedule: SamplingSchedule, cfg: FederatedConfig,
                         cohort_size: int, *, sampler=None):
    """The round's **select** piece, jittable on its own.

    Returns ``select(norms, n_samples, t, sample_key) -> (part, weights,
    cohort_ids)``: the sampler's participation mask and pre-dropout
    aggregation weights on the full ``(M,)`` arrays, plus the
    ``cohort_size`` cohort ids — the ``part > 0`` ids sorted ascending,
    padded with the lowest-id non-participants.  ``cohort_size`` must
    upper-bound the participant count (``ClientSampler.cohort_bucket``).
    Pass ``norms=None`` for non-adaptive samplers.
    """
    if not (0 < cohort_size <= cfg.num_clients):
        raise ValueError(
            f"cohort_size {cohort_size} not in (0, {cfg.num_clients}]")
    smp = sampler if sampler is not None else UniformSampler()
    M = cfg.num_clients

    def select(norms, n_samples, t, sample_key):
        with jax.named_scope("fl.local_update"):
            part, weights = smp.select(sample_key, schedule, t, M, n_samples,
                                       norms)
            ids = jnp.arange(M, dtype=jnp.int32)
            order = jnp.argsort(jnp.where(part > 0, ids, ids + M))
            cohort_ids = jnp.sort(order[:cohort_size])
        return part, weights, cohort_ids

    return select


def cohort_select(sample_key: jax.Array, schedule: SamplingSchedule, t,
                  num_clients: int, cohort_size: int):
    """The uniform sampler's cohort, as the server runs it: ``(cohort_ids,
    valid)`` with ``valid[i] = 1`` iff cohort member i is a true
    participant.  Identical participant set to
    :func:`repro.core.sampling.participation_mask` under the same key."""
    cfg = FederatedConfig(num_clients=num_clients, client=ClientConfig())
    select = make_store_selection(schedule, cfg, cohort_size)
    part, _, cohort_ids = select(None, jnp.ones((num_clients,), jnp.float32),
                                 t, sample_key)
    return cohort_ids, jnp.take(part, cohort_ids)


def make_store_compute(loss_fn: Callable, cfg: FederatedConfig, *,
                       codec=None, attack=None):
    """The round's **sweep** piece: the client side over cohort rows.

    Returns ``sweep(params, cohort_res, cohort_drift, cohort_batches,
    cohort_ids, mask_key) -> dict`` with keys ``uploads`` / ``wired``
    (pre-/post-wire stacked uploads), ``attacked`` (the payload the server
    decodes: ``wired`` with adversary rows perturbed — the same object when
    no attack is active), ``new_res`` / ``new_drift`` (post-round state
    candidates) and ``losses``.  Per-client mask keys are row i of
    ``split(mask_key, M)``, so client i's masking draw does not depend on
    which form ran it; ``cohort_ids=None`` sweeps all M clients.
    ``cohort_drift`` is None unless ``cfg.client.objective.uses_drift``.
    """
    attack = _active_attack(attack)
    M = cfg.num_clients

    def sweep(params, cohort_res, cohort_drift, cohort_batches, cohort_ids,
              mask_key):
        with jax.named_scope("fl.local_update"):
            mask_keys = _rows(jax.random.split(mask_key, M), cohort_ids)
            # A module global looked up at trace time, so a patched
            # ``federated.stacked_client_update`` takes effect.
            uploads, new_res, new_drift, losses = stacked_client_update(
                loss_fn, params, cohort_batches, mask_keys, cfg.client,
                cohort_res, cfg.error_feedback, cohort_drift)
        wired = roundtrip_stacked(codec, uploads)
        return {
            "uploads": uploads,
            "new_res": new_res,
            "new_drift": new_drift,
            "losses": losses,
            "wired": wired,
            "attacked": _attack_payload(attack, wired, mask_key, M,
                                        cohort_ids),
        }

    return sweep


def _make_barrier(cfg: FederatedConfig, *, aggregator=None, sampler=None,
                  hetero=None, attack=None):
    """The round's **barrier** piece.

    Returns ``(barrier, sampler, with_drop)``.  ``barrier(params, c, norms,
    drop_key) -> (new_params, res_rows, drift_rows, commit, norm_upd,
    metrics)`` consumes the sweep's dict ``c`` extended with the select's
    ``part`` / ``weights`` and ``cohort_ids`` (``None``: all M rows):

    * ``res_rows`` — the post-round residual candidates, EF wire-loss
      feedback folded in on the HONEST (uploads, wired) pair: a client's
      residual reflects what IT failed to ship, not what an attacker
      forged in its name;
    * ``drift_rows`` — the FedDyn drift candidates (None without drift);
    * ``commit`` — per cohort row, "this upload applied": it arrived and
      survived the quarantine gate.  A dropped or quarantined upload
      discards the whole local update, so its state rows must stay at the
      round entry; non-participants and padding rows never commit;
    * ``norm_upd`` — the adaptive sampler's norm-EMA rows (None otherwise;
      uncommitted rows carry the old value), fed by what the server saw:
      attacked rows feed their forged norms in.

    The in-program forms pass ``state=(residuals, drift)``, the full
    ``(M, …)`` trees, and ``c`` carrying the gathered round-entry rows
    ``cohort_res`` / ``cohort_drift``: the barrier then scatters the
    committed rows back (residuals only under error feedback; drift
    independent of it, since h_k tracks the honest local trajectory, not
    the wire) and returns ``(new_params, residuals, drift, norms,
    metrics)``.
    """
    smp = sampler if sampler is not None else UniformSampler()
    drop = None
    if hetero is not None:
        drop = jnp.asarray(hetero.drop_rates(cfg.num_clients), jnp.float32)
    agg_fn = _resolve_aggregator(aggregator, smp.normalize)
    attack = _active_attack(attack)

    def barrier(params, c, norms, drop_key, state=None):
        ids, part, payload = c["cohort_ids"], c["part"], c["attacked"]
        with jax.named_scope("fl.aggregate"):
            finite = _finite_rows(payload)
            arrived, weights = _apply_dropout(part, c["weights"], drop,
                                              drop_key, smp.normalize)
            valid, arr_c, w_c = _rows((part, arrived, weights), ids)
            new_params = agg_fn(params, _zero_rows(payload, finite),
                                w_c * finite, cfg.client.upload)
            commit = arr_c * finite
            res_rows = c["new_res"]
            if cfg.error_feedback and c["wired"] is not c["uploads"]:
                # Wire loss (int8 quantisation, slot truncation) is real
                # masked-out mass: feed it back like any other residual.
                res_rows = _wire_feedback(res_rows, c["uploads"], c["wired"])
            norm_upd = None
            if smp.adaptive:
                old = _rows(norms, ids)
                norm_upd = jnp.where(
                    commit > 0,
                    (1.0 - smp.ema) * old + smp.ema * _row_l2(payload), old)
            # The loss sum runs over the (M,) population in every form, so
            # its order (and bits) do not depend on the bucket.  An empty
            # round (the threshold sampler's random count can be 0) is a
            # no-op for the params; report NaN, not a fabricated 0.
            rows = jnp.arange(part.shape[0]) if ids is None else ids
            losses = jnp.zeros_like(part).at[rows].set(c["losses"] * valid)
            n_part = jnp.sum(part)
            metrics = {
                "mean_loss": jnp.where(
                    n_part > 0,
                    jnp.sum(losses) / jnp.maximum(n_part, 1.0), jnp.nan),
                "num_sampled": n_part,
                "quarantined": jnp.sum(arr_c * (1.0 - finite)),
            }
            if attack is not None:
                metrics["num_adversarial"] = jnp.sum(
                    part * _adversaries(attack, cfg.num_clients))
            if drop is not None:
                metrics["part_mask"] = part
                metrics["arrived_mask"] = arrived
                metrics["num_arrived"] = jnp.sum(arrived)
            if state is None:
                return (new_params, res_rows, c["new_drift"], commit,
                        norm_upd, metrics)
            residuals, drift = state
            if cfg.error_feedback:
                residuals = _scatter_rows(residuals, c["cohort_res"],
                                          res_rows, commit, ids)
            if drift is not None:
                drift = _scatter_rows(drift, c["cohort_drift"],
                                      c["new_drift"], commit, ids)
            if norm_upd is not None:
                norms = norm_upd if ids is None else \
                    norms.at[ids].set(norm_upd)
        return new_params, residuals, drift, norms, metrics

    return barrier, smp, drop is not None


# ---------------------------------------------------------------------------
# The forms
# ---------------------------------------------------------------------------
def _dispatch(select, sweep, gather: bool):
    """select → row gather → sweep, one traceable function with the
    signature of :func:`make_cohort_compute`'s ``compute``.  ``gather=False``
    (a cohort of all M clients) drops the gathers statically and reports
    ``cohort_ids=None``."""
    def compute(params, residuals, drift, norms, client_batches, n_samples,
                t, sample_key, mask_key):
        part, weights, ids = select(norms, n_samples, t, sample_key)
        ids = ids if gather else None
        with jax.named_scope("fl.local_update"):
            cohort_res, cohort_drift, cohort_batches = _rows(
                (residuals, drift, client_batches), ids)
        c = sweep(params, cohort_res, cohort_drift, cohort_batches, ids,
                  mask_key)
        return dict(c, part=part, weights=weights, cohort_ids=ids,
                    cohort_res=cohort_res, cohort_drift=cohort_drift)

    return compute


def make_cohort_compute(loss_fn: Callable, schedule: SamplingSchedule,
                        cfg: FederatedConfig, cohort_size: int, *,
                        codec=None, sampler=None, attack=None):
    """The round's client side — select, gather, sweep — and nothing after
    it: the async buffered engine (``repro.core.async_engine``) runs it as
    the round's *dispatch* and applies the uploads event by event.

    Returns ``compute(params, residuals, drift, norms, client_batches,
    n_samples, t, sample_key, mask_key) -> dict``: the sweep's keys (see
    :func:`make_store_compute`) plus ``part`` / ``weights`` (full ``(M,)``
    selection mask and pre-dropout weights), ``cohort_ids`` and
    ``cohort_res`` / ``cohort_drift`` (round-entry state rows, gathered).
    Pass ``norms=None`` for non-adaptive samplers and ``drift=None`` unless
    ``cfg.client.objective.uses_drift``.
    """
    select = make_store_selection(schedule, cfg, cohort_size,
                                  sampler=sampler)
    sweep = make_store_compute(loss_fn, cfg, codec=codec, attack=attack)
    return _dispatch(select, sweep, gather=True)


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


def make_cohort_round(loss_fn: Callable, schedule: SamplingSchedule,
                      cfg: FederatedConfig, cohort_size: int, *,
                      codec=None, aggregator=None, sampler=None, hetero=None,
                      attack=None):
    """Cohort-engine form of ``make_federated_round``: same signature(s) and
    math, but client_update runs over ``cohort_size`` (static) clients
    instead of ``cfg.num_clients``.

    One program: select, gather the cohort's batch shards and state rows,
    sweep, barrier, and scatter the committed rows back.  Requires
    ``cohort_size`` to upper-bound the sampler's participant count for
    every round it is dispatched to — the server guarantees this via
    ``ClientSampler.cohort_bucket`` (``SamplingSchedule.bucket_for`` for
    the default uniform sampler).  ``cohort_size == cfg.num_clients`` is
    the oracle: gathers and scatters drop out statically.
    """
    select = make_store_selection(schedule, cfg, cohort_size,
                                  sampler=sampler)
    sweep = make_store_compute(loss_fn, cfg, codec=codec, attack=attack)
    dispatch = _dispatch(select, sweep,
                         gather=cohort_size < cfg.num_clients)
    barrier, smp, with_drop = _make_barrier(
        cfg, aggregator=aggregator, sampler=sampler, hetero=hetero,
        attack=attack)

    def round_impl(params, residuals, drift, norms, client_batches,
                   n_samples, t, key):
        with jax.named_scope("fl.local_update"):
            sample_key, mask_key, drop_key = _split_round_key(key, with_drop)
        c = dispatch(params, residuals, drift, norms, client_batches,
                     n_samples, t, sample_key, mask_key)
        return barrier(params, c, norms, drop_key, state=(residuals, drift))

    return _wrap_round(round_impl, cfg.client.objective.uses_drift,
                       smp.adaptive)


def make_federated_round(loss_fn: Callable, schedule: SamplingSchedule,
                         cfg: FederatedConfig, *, codec=None, aggregator=None,
                         sampler=None, hetero=None, attack=None):
    """Build the full-population (oracle) round program: the cohort round
    with a cohort of all ``cfg.num_clients`` clients, gather-free.

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

    The barrier gates the decoded payload through the non-finite
    quarantine (``metrics["quarantined"]``): a NaN/Inf upload is
    zero-weighted and zeroed out instead of poisoning Θ, matching the
    async engine's gate.
    """
    return make_cohort_round(loss_fn, schedule, cfg, cfg.num_clients,
                             codec=codec, aggregator=aggregator,
                             sampler=sampler, hetero=hetero, attack=attack)


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
    round_fn = make_cohort_round(loss_fn, schedule, cfg, cohort_size,
                                 codec=codec, aggregator=aggregator,
                                 sampler=sampler, hetero=hetero,
                                 attack=attack)
    adaptive = sampler is not None and sampler.adaptive
    n_state = 2 + int(cfg.client.objective.uses_drift) + int(adaptive)

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


@dataclasses.dataclass(frozen=True)
class StoreRound:
    """The store-form round program, split at the store boundary.

    ``select`` and ``body`` are independently jittable; the driver
    (``FederatedServer._run_store``) moves state rows between them
    through a :class:`~repro.core.client_store.ClientStateStore`.  The
    flags tell the driver which optional state the pieces consume."""

    select: Callable   # (norms, n_samples, t, sample_key) -> (part, w, ids)
    body: Callable     # cohort-shaped sweep + barrier; see make_store_round
    adaptive: bool     # body consumes/updates the (M,) norm EMA
    with_drop: bool    # round key splits 3 ways (hetero dropout draw)
    error_feedback: bool  # residual rows need scattering back
    uses_drift: bool = False  # body consumes/emits FedDyn drift rows


def make_store_round(loss_fn: Callable, schedule: SamplingSchedule,
                     cfg: FederatedConfig, cohort_size: int, *,
                     codec=None, aggregator=None, sampler=None, hetero=None,
                     attack=None) -> StoreRound:
    """Store-form sibling of :func:`make_cohort_round`: the same pieces,
    with the state gather/scatter OUTSIDE the programs (DESIGN.md §11)::

        select(norms, n_samples, t, sample_key)     -> part, weights, ids
        store.gather(ids)                           -> cohort_res, drift
        body(params, cohort_res, cohort_drift, cohort_batches, cohort_ids,
             part, weights, norms, mask_key, drop_key)
            -> (new_params, new_rows, drift_rows, commit, norm_upd, metrics)
        store.scatter(ids, new_rows, commit); store.update_norms(ids, ...)

    ``body`` is the sweep then the barrier (see :func:`_make_barrier` for
    its outputs).  ``commit`` is ALWAYS computed; the driver gates the
    residual scatter on ``error_feedback`` and the drift scatter on
    ``uses_drift``.  Padding rows never commit, so a sharded gather
    returning zeros for a client the window forgot can only differ from
    dense on rows masked out of every reduction and never written back.
    """
    select = make_store_selection(schedule, cfg, cohort_size,
                                  sampler=sampler)
    sweep = make_store_compute(loss_fn, cfg, codec=codec, attack=attack)
    barrier, smp, with_drop = _make_barrier(
        cfg, aggregator=aggregator, sampler=sampler, hetero=hetero,
        attack=attack)

    def body(params, cohort_res, cohort_drift, cohort_batches, cohort_ids,
             part, weights, norms, mask_key, drop_key):
        c = sweep(params, cohort_res, cohort_drift, cohort_batches,
                  cohort_ids, mask_key)
        return barrier(params, dict(c, part=part, weights=weights,
                                    cohort_ids=cohort_ids), norms, drop_key)

    return StoreRound(select=select, body=body, adaptive=smp.adaptive,
                      with_drop=with_drop,
                      error_feedback=cfg.error_feedback,
                      uses_drift=cfg.client.objective.uses_drift)
