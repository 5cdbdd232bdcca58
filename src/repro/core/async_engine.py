"""Asynchronous buffered aggregation with a failure model (DESIGN.md §8).

The sync engines (``repro.core.federated``) end every round with a barrier:
aggregate once, after everyone.  On the simulated fleets of
``repro.core.hetero`` that barrier waits for the straggler.  This module is
the FedBuff-style alternative: the server consumes the round's upload
*arrival stream* (``hetero.arrival_stream``) as a time-ordered event queue
and applies a **buffer** of K uploads whenever it fills — clients that
arrive after a flush land in the next one, discounted by how stale their
base model has become.

Anatomy of one async round (:meth:`AsyncRoundRunner.run_round`):

1. **dispatch** — the engine-shared client-side sweep
   (``federated.make_cohort_compute``): selection → cohort gather → local
   updates → wire round-trip, one jitted program.  Identical bits to the
   sync cohort engine by construction.
2. **event loop** (host) — arrivals pop off a heap in ``(time, client)``
   order.  The failure model perturbs the fault-free stream:

   * *drops* — each transmission is lost with the fleet's per-client
     ``drop_rate``; lost uploads retry with exponential backoff
     (``backoff_s * 2^attempt`` + the client's re-upload wire time) up to
     ``max_retries`` times.  Horvitz-Thompson weights divide by the
     *policy* survival probability ``1 - q^(R+1)`` — the retry-aware
     analogue of the sync engine's ``1 - q`` — so the flushed sum stays
     unbiased (deadline censoring is the documented residual bias).
   * *deadline* — an absolute ``deadline_s`` or a ``deadline_quantile`` of
     the selected cohort's fault-free arrival times.  The first event past
     the deadline cuts the round: whatever arrived is aggregated, every
     pending client counts as a timeout, and — the graceful-degradation
     invariant — its error-feedback residual is left untouched, exactly as
     the sync engine treats a dropped upload.
   * *quarantine* — a validation gate at the codec decode boundary
     rejects uploads whose decoded payload contains NaN/Inf.  Quarantined
     rows never enter a flush, never update norm EMAs, and keep their
     round-entry residuals: a poisoned client cannot poison the global
     model or its own EF state.  (``corrupt_rate`` injects such payloads
     for chaos testing; with ``quarantine=False`` they propagate, which is
     the negative control.)

3. **flushes** — every time the buffer holds >= K arrivals the server
   applies one aggregation step over the buffered rows with weights
   ``w_i / (1 + s_i)^beta``: the sampler's weight debiased by staleness
   ``s_i`` = number of flushes applied since client i pulled the model.
   All events carrying the *same* timestamp drain before the buffer is
   checked, so simultaneous arrivals (the ``ideal`` fleet) form a single
   flush.  Leftovers flush once at round close.

**Keystone equivalence** (property-tested in tests/test_async.py): with
instant arrivals (ideal fleet), buffer K = m_t and no injected faults, the
round degenerates to dispatch + one flush of everyone at staleness 0 — and
is **bit-exact** vs the sync cohort engine (params, EF residuals, norm
EMAs).  Every ingredient preserves bits: the sweep is the shared compute,
the single flush multiplies weights by exactly ``1.0`` (staleness discount
at s=0, survival at q=0), and the masked ``jnp.where`` row-cleaning /
state commits pass untouched rows through unchanged.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codecs import IdentityCodec
from repro.core.federated import (_active_attack, _resolve_aggregator,
                                  _row_l2, _split_round_key, _wire_feedback,
                                  aot_compile, make_cohort_compute,
                                  make_store_compute, make_store_selection)
from repro.core.hetero import HeteroModel, arrival_stream

PyTree = Any

__all__ = ["AsyncConfig", "AsyncRoundRunner"]


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """The async engine's knobs: buffering, staleness, and the failure model.

    ``buffer_size`` fixes the flush threshold K; ``buffer_frac`` sizes it as
    a fraction of the round's m_t instead (at most one may be set; unset
    means K = m_t, the FedBuff-degenerates-to-sync point).
    ``staleness_beta`` is the exponent of the ``1/(1+s)^beta`` discount.
    Deadlines: ``deadline_s`` (absolute seconds) or ``deadline_quantile``
    (quantile of the cohort's fault-free arrival times; at most one).
    ``max_retries`` / ``backoff_s`` bound the retransmission policy;
    ``jitter_sigma`` adds per-round lognormal arrival jitter;
    ``corrupt_rate`` injects NaN payloads (chaos testing) and
    ``quarantine`` turns the decode-boundary validation gate on/off.

    ``max_round_stale`` switches staleness from flush-distance to
    **cross-round** distance (DESIGN.md §11.1): with S > 0, uploads cut by
    the deadline are not dropped but *carried* into subsequent rounds and
    applied with weight ``w/(1+s)^beta`` where ``s = t' - version[i]`` is
    the number of rounds since client i pulled its base model (the
    client-state store's per-client version vector).  An upload older than
    S rounds expires as a timeout; a carried upload superseded by a fresh
    dispatch of the same client is discarded.  S = 0 (default) keeps the
    original within-round flush-count staleness bit-identically.
    """

    buffer_size: int | None = None
    buffer_frac: float | None = None
    staleness_beta: float = 0.5
    deadline_s: float | None = None
    deadline_quantile: float | None = None
    max_retries: int = 2
    backoff_s: float = 0.5
    jitter_sigma: float = 0.0
    corrupt_rate: float = 0.0
    quarantine: bool = True
    max_round_stale: int = 0

    def __post_init__(self):
        """Reject contradictory or out-of-range knob combinations."""
        if self.buffer_size is not None and self.buffer_frac is not None:
            raise ValueError("set at most one of buffer_size / buffer_frac")
        if self.buffer_size is not None and self.buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {self.buffer_size}")
        if self.buffer_frac is not None and not 0.0 < self.buffer_frac <= 1.0:
            raise ValueError(
                f"buffer_frac must be in (0, 1], got {self.buffer_frac}")
        if self.staleness_beta < 0.0:
            raise ValueError(
                f"staleness_beta must be >= 0, got {self.staleness_beta}")
        if self.deadline_s is not None and self.deadline_quantile is not None:
            raise ValueError(
                "set at most one of deadline_s / deadline_quantile")
        if self.deadline_s is not None and self.deadline_s <= 0.0:
            raise ValueError(f"deadline_s must be > 0, got {self.deadline_s}")
        if (self.deadline_quantile is not None
                and not 0.0 < self.deadline_quantile <= 1.0):
            raise ValueError(
                f"deadline_quantile must be in (0, 1], got "
                f"{self.deadline_quantile}")
        if self.max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {self.max_retries}")
        if self.backoff_s < 0.0:
            raise ValueError(f"backoff_s must be >= 0, got {self.backoff_s}")
        if self.jitter_sigma < 0.0:
            raise ValueError(
                f"jitter_sigma must be >= 0, got {self.jitter_sigma}")
        if not 0.0 <= self.corrupt_rate <= 1.0:
            raise ValueError(
                f"corrupt_rate must be in [0, 1], got {self.corrupt_rate}")
        if self.max_round_stale < 0:
            raise ValueError(
                f"max_round_stale must be >= 0, got {self.max_round_stale}")

    def buffer_for(self, m: int) -> int:
        """Flush threshold K for a round expecting ``m`` participants."""
        if self.buffer_size is not None:
            return self.buffer_size
        if self.buffer_frac is not None:
            return max(1, int(np.ceil(self.buffer_frac * m)))
        return max(1, m)


class AsyncRoundRunner:
    """Per-strategy async round executor: owns the compiled-program caches
    and the fleet traits; :meth:`run_round` runs one buffered round.

    The jitted surface is three small programs per cohort bucket — the
    engine-shared dispatch sweep, the per-flush aggregation, and the
    round-close state commit — each AOT-compiled once per (bucket, aval)
    signature with the compile time metered out of the round clock, same
    discipline as ``FederatedServer``'s sync paths.
    """

    def __init__(self, strategy, loss_fn, num_clients: int,
                 async_cfg: AsyncConfig | None = None, store=None):
        self.strategy = strategy
        self.loss_fn = loss_fn
        self.num_clients = num_clients
        acfg = async_cfg
        if acfg is None:
            acfg = getattr(strategy, "async_cfg", None)
        self.acfg = acfg if acfg is not None else AsyncConfig()
        # The client-state store (DESIGN.md §11).  A sharded store reroutes
        # dispatch through the store-form sweep (residual gather/scatter
        # outside the program); cross-round staleness needs the store's
        # per-client version vector either way.
        self.store = store
        self._crossround = self.acfg.max_round_stale > 0
        if self._crossround and store is None:
            raise ValueError(
                "max_round_stale > 0 (cross-round staleness) requires a "
                "ClientStateStore — the per-client model-version state "
                "lives there")
        # In-flight uploads carried across round boundaries (cross-round
        # mode): one dict per upload with its payload/residual rows, base
        # weight, dispatch round and remaining lateness.
        self._pending: list = []
        self.schedule = strategy.sampling
        self.smp = strategy.sampler
        self.cfg = strategy.federated_config(num_clients)
        # FedDyn drift rides the store on BOTH backends (dense included) so
        # run_round's signature stays drift-free; commits go through
        # store.scatter(..., tree="drift") with the applied-rows mask.
        self._uses_drift = self.cfg.client.objective.uses_drift
        if self._uses_drift:
            if store is None:
                raise ValueError(
                    f"strategy {strategy.name!r} carries FedDyn drift "
                    "state; the async engine needs a ClientStateStore "
                    "built with extra_trees={'drift': ...}")
            if "drift" not in store.trees:
                raise ValueError(
                    "async engine with a FedDyn objective requires the "
                    "store to hold a 'drift' tree (extra_trees=)")
        # The clock/fault traits: an explicit fleet, or ideal (instant
        # arrivals, no drops) when the strategy has no hetero model.  The
        # ROUND KEY split still mirrors the sync engine's, which branches
        # on whether hetero is set — bit-exactness depends on it.
        hetero = strategy.hetero if strategy.hetero is not None \
            else HeteroModel(profile="ideal")
        self.traits = hetero.client_traits(num_clients)
        self._with_drop = strategy.hetero is not None
        self._agg_fn = _resolve_aggregator(strategy.aggregator,
                                           self.smp.normalize)
        # Mirrors roundtrip_stacked's pass-through condition — the static
        # analogue of the sync engines' `wired is not uploads` check.
        self._wire_feedback = not (strategy.codec is None
                                   or isinstance(strategy.codec, IdentityCodec))
        self._inject = self.acfg.corrupt_rate > 0.0
        # Byzantine adversaries (DESIGN.md §9): the dispatch sweep hands us
        # the attacked payload; its non-finite rows (e.g. the "nan" attack)
        # land in the same quarantine gate as corrupt_rate injections.
        self.attack = _active_attack(getattr(strategy, "attack", None))
        self._adv = (self.attack.adversary_mask(num_clients)
                     if self.attack is not None else None)
        # Per-client probability that ALL max_retries+1 transmissions drop;
        # HT weights divide by its complement (exact 1.0 on no-drop fleets).
        q = np.asarray(self.traits.drop_rate, np.float64)
        self._survival = (1.0 - q ** (self.acfg.max_retries + 1)).astype(
            np.float32)
        # AOT executables, keyed by (program, bucket, input avals); see
        # federated.aot_compile.
        self._aot_cache: Dict[Any, Any] = {}

    # ---- jitted round pieces ----------------------------------------------
    def _gate_impl(self, wired, corrupt_c):
        """Chaos injection + the quarantine gate's validity check: returns
        ``(wired, finite_rows)`` where row i is finite iff every element of
        cohort member i's decoded upload is."""
        if self._inject:
            def poison(u):
                cm = corrupt_c.reshape((-1,) + (1,) * (u.ndim - 1))
                return jnp.where(cm > 0, jnp.full_like(u, jnp.nan), u)
            wired = jax.tree.map(poison, wired)
        finite = None
        for leaf in jax.tree_util.tree_leaves(wired):
            ok = jnp.all(jnp.isfinite(leaf.astype(jnp.float32)),
                         axis=tuple(range(1, leaf.ndim)))
            finite = ok if finite is None else finite & ok
        return wired, finite.astype(jnp.float32)

    def _flush_impl(self, params, wired, w_flush, keep):
        """One buffer flush: aggregate the rows with nonzero ``w_flush``.

        ``keep`` masks rows allowed to touch the arithmetic at all —
        non-finite rows are *zeroed out*, not just zero-weighted, because
        ``0 * NaN`` is NaN: a quarantined payload must not reach the sum
        even with weight 0.  On all-finite rounds ``keep`` is all-ones and
        the ``where`` is a bit-exact pass-through.
        """
        def clean(u):
            km = keep.reshape((-1,) + (1,) * (u.ndim - 1))
            return jnp.where(km > 0, u, jnp.zeros_like(u))

        cleaned = jax.tree.map(clean, wired)
        return self._agg_fn(params, cleaned, w_flush, self.cfg.client.upload)

    def _close_impl(self, residuals, norms, cohort_ids, cohort_res, new_res,
                    uploads, wired, payload, applied_c):
        """Round-close state commit: EF residuals advance and norm EMAs
        update only for cohort rows whose upload was APPLIED (arrived
        before the deadline, survived quarantine, entered a flush) —
        timeouts, permanent drops and quarantined rows keep their
        round-entry state, the async analogue of the sync engines'
        arrived-mask gating.  EF wire-loss feedback stays on the HONEST
        (uploads, wired) pair (a residual reflects what the client failed
        to ship, not what an attacker forged); the norm tracker observes
        ``payload`` — what the server actually saw."""
        if self.cfg.error_feedback:
            if self._wire_feedback:
                new_res = _wire_feedback(new_res, uploads, wired)

            def scatter(old, new, old_cohort):
                am = applied_c.reshape((-1,) + (1,) * (new.ndim - 1))
                kept = jnp.where(am > 0, new, old_cohort)
                return old.at[cohort_ids].set(kept)

            residuals = jax.tree.map(scatter, residuals, new_res, cohort_res)
        if self.smp.adaptive:
            obs = _row_l2(payload)
            old_c = jnp.take(norms, cohort_ids)
            upd = jnp.where(applied_c > 0,
                            (1.0 - self.smp.ema) * old_c + self.smp.ema * obs,
                            old_c)
            norms = norms.at[cohort_ids].set(upd)
        return residuals, norms

    def _close_rows_impl(self, norms, cohort_ids, new_res, uploads, wired,
                         payload, applied_c):
        """Cohort-level round close for the store-form path: same math as
        :meth:`_close_impl`, but the commit-masked scatter is the store's
        job — this just finalizes the residual candidate rows (wire-loss
        feedback folded in) and the cohort's norm-EMA rows."""
        if self.cfg.error_feedback and self._wire_feedback:
            new_res = _wire_feedback(new_res, uploads, wired)
        norm_upd = None
        if self.smp.adaptive:
            obs = _row_l2(payload)
            old_c = jnp.take(norms, cohort_ids)
            norm_upd = jnp.where(
                applied_c > 0,
                (1.0 - self.smp.ema) * old_c + self.smp.ema * obs, old_c)
        return new_res, norm_upd

    # ---- the round --------------------------------------------------------
    def run_round(self, params, residuals, norms, client_batches, n_samples,
                  t: int, key, *, cohort_size: int, flops: float,
                  wire_bytes: int):
        """Run one async buffered round.

        Same state contract as the sync round programs — returns
        ``(params, residuals, norms, stats)`` with ``norms`` passed through
        (possibly ``None``) for non-adaptive samplers — plus the host-side
        ``stats`` dict the server turns into a ``RoundRecord``.
        ``cohort_size`` must upper-bound the sampler's participant count
        for round ``t`` (use ``ClientSampler.cohort_bucket``).

        On a sharded store the dispatch reroutes through the store-form
        sweep (``residuals`` is ignored — pass None) and ALL state commits
        go through ``self.store``; the returned ``residuals`` is None.  In
        cross-round mode (``max_round_stale > 0``) deadline-cut uploads
        are carried into later rounds instead of timing out — see
        :class:`AsyncConfig`.
        """
        acfg = self.acfg
        M = self.num_clients
        compile_s = 0.0
        sharded = self.store is not None and self.store.kind != "dense"

        # 1. dispatch: identical key split + client-side sweep to the sync
        # cohort engine.  The sharded path runs the same math split at the
        # store boundary (selection → store gather → cohort-shaped sweep).
        sample_key, mask_key, drop_key = _split_round_key(
            jnp.asarray(key), self._with_drop)
        t_arr = jnp.asarray(t, jnp.float32)
        if sharded:
            sel_args = (norms, n_samples, t_arr, sample_key)
            sel, dt = aot_compile(
                self._aot_cache, "select", cohort_size,
                lambda: make_store_selection(self.schedule, self.cfg,
                                             cohort_size, sampler=self.smp),
                sel_args)
            compile_s += dt
            part_dev, weights_dev, ids_dev = sel(*sel_args)
            ids_np = np.asarray(ids_dev)
            cohort_res = self.store.gather(ids_np)
            cohort_drift = (self.store.gather(ids_np, tree="drift")
                            if self._uses_drift else None)
            if callable(client_batches):
                cohort_batches = client_batches(ids_np)
            else:
                cohort_batches = jax.tree.map(
                    lambda x: jnp.take(x, ids_dev, axis=0), client_batches)
            cargs = (params, cohort_res, cohort_drift, cohort_batches,
                     ids_dev, mask_key)
            comp, dt = aot_compile(
                self._aot_cache, "sweep", cohort_size,
                lambda: make_store_compute(self.loss_fn, self.cfg,
                                           codec=self.strategy.codec,
                                           attack=self.attack),
                cargs)
            compile_s += dt
            out = dict(comp(*cargs))
            out.update(part=part_dev, weights=weights_dev,
                       cohort_ids=ids_dev, cohort_res=cohort_res)
        else:
            drift_dense = (self.store.dense_view("drift")
                           if self._uses_drift else None)
            compute_args = (params, residuals, drift_dense, norms,
                            client_batches, n_samples, t_arr, sample_key,
                            mask_key)
            compute, dt = aot_compile(
                self._aot_cache, "compute", cohort_size,
                lambda: make_cohort_compute(
                    self.loss_fn, self.schedule, self.cfg, cohort_size,
                    codec=self.strategy.codec, sampler=self.smp,
                    attack=self.attack),
                compute_args)
            compile_s += dt
            out = compute(*compute_args)

        part = np.asarray(out["part"])
        cohort_ids = np.asarray(out["cohort_ids"])
        losses = np.asarray(out["losses"], np.float64)
        B = int(cohort_ids.shape[0])
        row_of = {int(cid): i for i, cid in enumerate(cohort_ids)}
        # Θ_t went out to this round's participants: record the model
        # version each carries — what cross-round staleness measures
        # against (s = 0 for everything applied within the round).
        if self.store is not None:
            self.store.mark_dispatched(np.flatnonzero(part > 0), t)

        # Host-side randomness (corrupt draws, arrival jitter, drop draws)
        # is seeded from the round's drop subkey so reruns are exact replays.
        seed_key = drop_key if drop_key is not None else key
        rng = np.random.default_rng(
            [int(x) for x in np.asarray(seed_key, np.uint32).ravel()])

        # 2. adversary payload + chaos injection + quarantine validity
        # flags.  ``payload`` is what the server decodes (attacked rows
        # perturbed, possibly NaN-poisoned); ``wired`` stays the honest
        # wire round-trip the EF state commit consumes.
        wired = out["wired"]
        payload = out["attacked"]
        corrupt = np.zeros((M,), np.float32)
        if self._inject:
            corrupt = (rng.random(M) < acfg.corrupt_rate).astype(np.float32)
        if self._inject or acfg.quarantine:
            gate_args = (payload, jnp.asarray(corrupt[cohort_ids]))
            gate, dt = aot_compile(self._aot_cache, "gate", cohort_size,
                                   lambda: self._gate_impl, gate_args)
            compile_s += dt
            payload, finite_dev = gate(*gate_args)
            finite_c = np.asarray(finite_dev)
        else:
            finite_c = np.ones((B,), np.float32)

        # 3. the arrival-time stream and its failure-model perturbation.
        first = list(arrival_stream(self.traits, part, flops, wire_bytes,
                                    rng=rng, jitter_sigma=acfg.jitter_sigma))
        deadline = np.inf
        if acfg.deadline_s is not None:
            deadline = float(acfg.deadline_s)
        elif acfg.deadline_quantile is not None and first:
            deadline = float(np.quantile(
                np.asarray([ts for ts, _ in first], np.float64),
                acfg.deadline_quantile))
        # Heap entries are ``(time, client, attempt, carried_idx)`` with
        # carried_idx = -1 for this round's own transmissions; cross-round
        # mode injects last rounds' still-in-flight uploads at their
        # remaining lateness.
        heap: list = [(ts, cid, 0, -1) for ts, cid in first]
        heapq.heapify(heap)

        q = np.asarray(self.traits.drop_rate, np.float64)
        resend = np.asarray(self.traits.upload_time_s(wire_bytes), np.float64)
        m_t = int(self.schedule.num_clients_host(t, M))
        K = acfg.buffer_for(m_t)

        # Sampler weights for the cohort rows, host-side; Horvitz-Thompson
        # weights are debiased by the retry policy's survival probability.
        base_w = np.asarray(out["weights"], np.float32)[cohort_ids]
        if not self.smp.normalize:
            base_w = base_w / self._survival[cohort_ids]
        keep_dev = jnp.asarray(finite_c if acfg.quarantine
                               else np.ones((B,), np.float32))

        applied_rows = np.zeros((B,), np.float32)
        buffer_rows: list = []       # ("cur", cohort_row) | ("carried", idx)
        carried_applied: list = []
        arrivals = timeouts = retries = quarantined = dropped = sends = 0
        flushes = 0
        staleness_sum = 0.0
        applied_times: list = []
        close_time = 0.0

        # Cross-round carry-in: last rounds' deadline-cut uploads re-enter
        # the event queue at their remaining lateness, unless superseded by
        # a fresh dispatch of the same client (it re-downloaded Θ and
        # recomputed — the in-flight upload is obsolete) or expired past
        # the max_round_stale window; both count as timeouts.
        carried_in: list = []
        if self._crossround and self._pending:
            participants = set(np.flatnonzero(part > 0).tolist())
            for e in self._pending:
                s = int(self.store.staleness(np.asarray([e["cid"]]), t)[0])
                if e["cid"] in participants or s > acfg.max_round_stale:
                    timeouts += 1
                    continue
                heapq.heappush(
                    heap, (e["lateness"], e["cid"], 0, len(carried_in)))
                carried_in.append(e)
            self._pending = []

        def carry_entry(row, cid, lateness):
            """Snapshot one cohort row as an in-flight cross-round upload:
            the decoded payload row (aggregation + norm observation), the
            finalized EF residual candidate (wire-loss feedback folded
            in), its base weight, quarantine flag and dispatch round."""
            res_row = None
            if self.cfg.error_feedback:
                if self._wire_feedback:
                    res_row = jax.tree.map(
                        lambda n, u, w: n[row] + (u[row] - w[row]),
                        out["new_res"], out["uploads"], wired)
                else:
                    res_row = jax.tree.map(lambda x: x[row], out["new_res"])
            drift_row = None
            if self._uses_drift:
                drift_row = jax.tree.map(lambda x: x[row], out["new_drift"])
            return {"cid": int(cid), "w": float(base_w[row]),
                    "finite": float(finite_c[row]), "round": int(t),
                    "lateness": float(lateness),
                    "payload": jax.tree.map(lambda x: x[row], payload),
                    "res": res_row, "drift": drift_row}

        def do_flush():
            """Aggregate the current buffer at the current staleness:
            flush-count discount in the classic mode, per-row round
            distance ``1/(1+s)^beta`` (s from the store's version vector)
            in cross-round mode, where carried rows join the same flush as
            this round's arrivals."""
            nonlocal params, flushes, staleness_sum, compile_s
            if not buffer_rows:
                return
            cur = [i for kind, i in buffer_rows if kind == "cur"]
            car = [i for kind, i in buffer_rows if kind == "carried"]
            member = np.zeros((B,), np.float32)
            member[cur] = 1.0
            if self._crossround:
                # Fresh rows pulled Θ this round: s = 0, discount exactly
                # 1.0 — the keystone degeneration survives cross-round
                # mode untouched.
                w_flush = jnp.asarray(base_w * member)
                flush_payload, keep = payload, keep_dev
                if car:
                    cids = np.asarray([carried_in[i]["cid"] for i in car])
                    s_car = self.store.staleness(cids, t).astype(np.float64)
                    d_car = 1.0 / (1.0 + s_car) ** acfg.staleness_beta
                    w_car = (np.asarray([carried_in[i]["w"] for i in car],
                                        np.float64) * d_car)
                    car_payload = jax.tree.map(
                        lambda *rows: jnp.stack(rows),
                        *[carried_in[i]["payload"] for i in car])
                    flush_payload = jax.tree.map(
                        lambda a, b: jnp.concatenate([a, b]),
                        payload, car_payload)
                    w_flush = jnp.concatenate(
                        [w_flush, jnp.asarray(w_car, jnp.float32)])
                    # carried rows were quarantine-gated at arrival, so
                    # every buffered one is finite
                    keep = jnp.concatenate(
                        [keep_dev, jnp.ones((len(car),), jnp.float32)])
                    staleness_sum += float(s_car.sum())
            else:
                s = flushes
                discount = np.float32(1.0 / (1.0 + s) ** acfg.staleness_beta)
                w_flush = jnp.asarray(base_w * member * discount)
                flush_payload, keep = payload, keep_dev
                staleness_sum += float(s) * len(buffer_rows)
            flush_args = (params, flush_payload, w_flush, keep)
            flush, dt = aot_compile(self._aot_cache, "flush", cohort_size,
                                    lambda: self._flush_impl, flush_args)
            compile_s += dt
            params = flush(*flush_args)
            applied_rows[cur] = 1.0
            carried_applied.extend(carried_in[i] for i in car)
            flushes += 1
            buffer_rows.clear()

        # 4. the event loop.
        while heap:
            t_now = heap[0][0]
            if t_now > deadline:
                # Deadline cut: the clients DID transmit (bytes were
                # spent); the server just stops listening.  Classic mode
                # times everything pending out; cross-round mode carries
                # it — this round's own rows snapshot their computed
                # upload, already-carried rows keep riding.
                while heap:
                    ev_t, cid, _, ci = heapq.heappop(heap)
                    if ci >= 0:
                        self._pending.append(
                            dict(carried_in[ci], lateness=ev_t - deadline))
                        continue
                    sends += 1
                    if self._crossround:
                        self._pending.append(carry_entry(
                            row_of[int(cid)], cid, ev_t - deadline))
                    else:
                        timeouts += 1
                close_time = max(close_time, deadline)
                break
            # Drain every event sharing this exact timestamp before any
            # flush check — simultaneous arrivals join the same flush,
            # which is what collapses the ideal fleet to one sync step.
            while heap and heap[0][0] == t_now:
                _, cid, attempt, ci = heapq.heappop(heap)
                if ci >= 0:
                    # A carried upload lands: no drop draw (its transport
                    # already happened last round), same quarantine gate.
                    e = carried_in[ci]
                    close_time = max(close_time, t_now)
                    if acfg.quarantine and e["finite"] == 0.0:
                        quarantined += 1
                        continue
                    arrivals += 1
                    applied_times.append(t_now)
                    buffer_rows.append(("carried", ci))
                    continue
                sends += 1
                if q[cid] > 0.0 and rng.random() < q[cid]:
                    if attempt < acfg.max_retries:
                        delay = (acfg.backoff_s * (2.0 ** attempt)
                                 + float(resend[cid]))
                        heapq.heappush(
                            heap, (t_now + delay, cid, attempt + 1, -1))
                        retries += 1
                    else:
                        dropped += 1
                    continue
                row = row_of[int(cid)]
                close_time = max(close_time, t_now)
                if acfg.quarantine and finite_c[row] == 0.0:
                    quarantined += 1
                    continue
                arrivals += 1
                applied_times.append(t_now)
                buffer_rows.append(("cur", row))
            if len(buffer_rows) >= K:
                do_flush()
        do_flush()  # leftovers (buffer below K at round close) flush once

        # 5. round-close state commit.  The sharded path finalizes
        # cohort-shaped rows and commits them through the store; the dense
        # path scatters into the full (M, …) arrays in-program, exactly as
        # before.
        applied_dev = jnp.asarray(applied_rows)
        if sharded:
            close_args = (norms, out["cohort_ids"], out["new_res"],
                          out["uploads"], wired, payload, applied_dev)
            close, dt = aot_compile(self._aot_cache, "close-rows",
                                    cohort_size,
                                    lambda: self._close_rows_impl,
                                    close_args)
            compile_s += dt
            rows, norm_upd = close(*close_args)
            if self.cfg.error_feedback:
                self.store.scatter(ids_np, rows, applied_rows, t)
            if self.smp.adaptive:
                self.store.update_norms(ids_np, norm_upd)
                norms = self.store.norms
            residuals = None
        else:
            close_args = (residuals, norms, out["cohort_ids"],
                          out["cohort_res"], out["new_res"], out["uploads"],
                          wired, payload, applied_dev)
            close, dt = aot_compile(self._aot_cache, "close", cohort_size,
                                    lambda: self._close_impl, close_args)
            compile_s += dt
            residuals, norms = close(*close_args)

        if self._uses_drift:
            # Drift commits through the store on BOTH backends: the same
            # commit-masked where→set the sync engines run in-program, so
            # run_round's signature stays drift-free.
            self.store.scatter(cohort_ids, out["new_drift"], applied_rows,
                               t, tree="drift")

        # Late commits for carried uploads applied this round: EF residual
        # and norm EMA advance at APPLY time.  Their owners were not
        # redispatched this round (supersession dropped those), so these
        # writes touch rows the round-close commit left untouched.
        for e in carried_applied:
            cid = e["cid"]
            if e["res"] is not None:
                if sharded:
                    self.store.scatter(
                        np.asarray([cid]),
                        jax.tree.map(lambda x: x[None], e["res"]),
                        np.ones((1,), np.float32), t)
                else:
                    residuals = jax.tree.map(
                        lambda old, r: old.at[cid].set(r),
                        residuals, e["res"])
            if self._uses_drift and e.get("drift") is not None:
                self.store.scatter(
                    np.asarray([cid]),
                    jax.tree.map(lambda x: x[None], e["drift"]),
                    np.ones((1,), np.float32), t, tree="drift")
            if self.smp.adaptive:
                obs = _row_l2(
                    jax.tree.map(lambda x: x[None], e["payload"]))[0]
                upd = ((1.0 - self.smp.ema) * norms[cid]
                       + self.smp.ema * obs)
                if sharded:
                    self.store.update_norms(np.asarray([cid]),
                                            jnp.asarray([upd]))
                    norms = self.store.norms
                else:
                    norms = norms.at[cid].set(upd)

        valid = part[cohort_ids].astype(np.float64)
        n_part = float(part.sum())
        n_applied = float(applied_rows.sum()) + len(carried_applied)
        mean_loss = (float((losses * valid).sum() / max(valid.sum(), 1.0))
                     if n_part > 0 else float("nan"))
        median_applied = (float(np.median(np.asarray(applied_times)))
                          if applied_times else 0.0)
        stats = {
            "mean_loss": mean_loss,
            "num_sampled": int(n_part),
            "adversarial": (int((part * self._adv).sum())
                            if self._adv is not None else 0),
            "arrivals": arrivals,
            "timeouts": timeouts,
            "retries": retries,
            "quarantined": quarantined,
            "dropped": dropped,
            "sends": sends,
            "flushes": flushes,
            "buffer_size": K,
            "carried": len(carried_applied),
            "pending": len(self._pending),
            "mean_staleness": (staleness_sum / n_applied
                               if n_applied > 0 else 0.0),
            "sim_round_s": close_time,
            "straggler_s": close_time - median_applied,
            "deadline_s": deadline if np.isfinite(deadline) else None,
            "compile_s": compile_s,
        }
        return params, residuals, norms, stats
