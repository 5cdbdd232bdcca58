"""Central-server training loop (paper Alg. 1 / Alg. 3 outer procedure).

``FederatedServer`` owns the global model, runs R communication rounds,
meters transport bytes per round, and evaluates on a held-out set.  The
scenario it runs — sampling schedule, mask policy, wire codec, aggregation
rule, client hyperparameters — is a single
:class:`repro.core.strategy.FedStrategy`; construct the server with
:meth:`FederatedServer.from_strategy` (the legacy ``(loss_fn, schedule,
cfg, ...)`` kwargs still work behind a ``DeprecationWarning`` shim that
synthesizes an equivalent strategy).

Transport is metered by the strategy's codec: every client upload is
round-tripped through the codec's wire format inside the round program, and
``RoundRecord.transport_bytes`` counts the EXACT serialized bytes of that
wire pytree (``UploadCodec.wire_bytes``, shape-only via ``eval_shape``) —
not the ``pytree_payload_bytes`` estimate earlier revisions reported.

Two further scenario axes ride on the strategy (DESIGN.md §5): adaptive
client samplers (importance/threshold) make the server carry a per-client
update-norm tracker as round-program state next to the error-feedback
residuals, and a ``HeteroModel`` fleet adds in-round upload dropout plus
host-side clock simulation — ``RoundRecord.sim_round_s`` (straggler
wall-clock on the simulated fleet), ``straggler_s`` and ``dropped``.

Three execution engines (DESIGN.md §3.5, §8):

* ``engine="cohort"`` (default): per round, only the sampled cohort is
  materialized and executed — the cohort buffer size is bucketed to
  ``SamplingSchedule.bucket_ladder`` so recompiles stay O(log M) as c(t)
  anneals.  Consecutive rounds sharing a bucket are folded into one
  ``lax.scan`` dispatch.  Rounds whose bucket is the full population fall
  through to the oracle program, so full-participation runs are
  bit-identical to the legacy path.
* ``engine="full"``: the original full-population vmap (every registered
  client runs; non-participants are zero-weighted) — kept as the oracle
  the cohort engine is property-tested against, under every registry
  preset (tests/test_strategy.py).
* ``engine="async"``: FedBuff-style asynchronous buffered aggregation
  (``repro.core.async_engine``) — uploads apply as they *arrive* on the
  strategy's simulated fleet, K at a time with staleness-discounted
  weights, under a failure model (deadlines, retry/backoff, upload
  quarantine) configured by ``strategy.async_cfg``.  Degenerates
  bit-exactly to the cohort engine on an instant fleet with no faults
  (property-tested in tests/test_async.py); per-round fault accounting
  lands in the new ``RoundRecord`` fields (arrivals, timeouts, retries,
  quarantined, flushes, mean_staleness).

The server also carries a persistent round counter: ``save_state`` /
``restore_state`` round-trip the full training state (params, EF
residuals, sampler norm EMAs, RNG key, round counter) through
``repro.checkpoint.checkpoint``, and ``run()`` continues from the restored
round — a resumed run is bit-identical to an uninterrupted one
(tests/test_async.py::test_crash_resume_bit_exact, both engines).

Each distinct (bucket, segment-length) program is AOT-compiled once and
cached; compile time is recorded on the triggering round's
``RoundRecord.compile_s`` instead of polluting ``wall_s``, so bench JSON
reflects steady-state per-round cost.

This is the *simulation* driver used by the paper-reproduction benchmarks
(Figs. 3-9).  The pod-scale driver is ``repro.launch.train``.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import local_update_flops
from repro.core.client_store import ClientStateStore, DenseStore
from repro.core.compression import pytree_num_params
from repro.core.federated import (FederatedConfig, _split_round_key,
                                  aot_compile)
from repro.core.hetero import simulate_round
from repro.core.sampling import SamplingSchedule

PyTree = Any

__all__ = ["RoundRecord", "FederatedServer"]


@dataclasses.dataclass
class RoundRecord:
    """Per-round ledger entry: who participated, what it cost (measured
    wall-clock, exact wire bytes) and — when the strategy carries a
    :class:`repro.core.hetero.HeteroModel` — what the round would have cost
    on the simulated fleet (``sim_round_s`` straggler wall-clock,
    ``straggler_s`` tail above the median arrival, ``dropped`` lost
    uploads)."""

    round: int
    num_sampled: int
    mean_loss: float
    transport_units: float      # full-model-upload units this round (Eq. 6 basis)
    transport_bytes: int        # EXACT wire bytes (codec-encoded uploads)
    eval_metric: Optional[float] = None
    wall_s: float = 0.0         # steady-state execution time (compile excluded)
    compile_s: float = 0.0      # program build time; nonzero on bucket-change rounds
    cohort_size: int = 0        # padded cohort buffer actually executed
    sim_round_s: float = 0.0    # simulated fleet wall-clock (hetero only)
    straggler_s: float = 0.0    # sim straggler tail: max - median arrival
    dropped: int = 0            # uploads lost on the simulated fleet
    # --- async-engine accounting (engine="async" only; DESIGN.md §8) ---
    arrivals: int = 0           # uploads accepted into a buffer flush
    timeouts: int = 0           # uploads cut by the round deadline
    retries: int = 0            # retransmissions scheduled after drops
    quarantined: int = 0        # uploads rejected at the decode gate (ALL engines, §9)
    flushes: int = 0            # buffer flushes applied this round
    mean_staleness: float = 0.0  # mean flush-count staleness of applied rows
    # --- cross-round staleness (max_round_stale > 0 only; DESIGN.md §11.1) ---
    carried: int = 0            # deadline-cut uploads applied from earlier rounds
    pending: int = 0            # uploads still parked for a later round
    # --- Byzantine accounting (strategy.attack set; DESIGN.md §9) ---
    adversarial: int = 0        # adversary-controlled participants this round


class FederatedServer:
    """Owns Θ_t; runs rounds; meters communication."""

    def __init__(self, loss_fn: Callable = None, schedule: SamplingSchedule = None,
                 cfg: FederatedConfig = None, init_params: PyTree = None,
                 eval_fn: Optional[Callable] = None, seed: int = 0,
                 engine: str = "cohort", scan_rounds: bool = True, *,
                 strategy=None, num_clients: int = None,
                 store: Optional[ClientStateStore] = None):
        """Legacy kwargs constructor — DEPRECATED shim for one release.

        Prefer :meth:`from_strategy`.  The ``(schedule, cfg)`` pair is
        converted to an equivalent :class:`FedStrategy` (codec derived from
        the masking config), so both paths run the identical round
        program.
        """
        if strategy is None:
            if schedule is None or cfg is None:
                raise TypeError(
                    "FederatedServer needs either strategy=/num_clients= or "
                    "the legacy (schedule, cfg) pair")
            warnings.warn(
                "FederatedServer(loss_fn, schedule, cfg, ...) is deprecated; "
                "use FederatedServer.from_strategy(strategy, loss_fn, "
                "init_params, num_clients, ...) with a repro.core.strategy."
                "FedStrategy (see strategy.get presets)",
                DeprecationWarning, stacklevel=2)
            from repro.core.strategy import FedStrategy
            strategy = FedStrategy.from_components(
                "legacy", schedule, cfg.client.masking,
                local_epochs=cfg.client.local_epochs,
                learning_rate=cfg.client.learning_rate,
                momentum=cfg.client.momentum,
                upload=cfg.client.upload,
                error_feedback=cfg.error_feedback)
            num_clients = cfg.num_clients
        if engine not in ("cohort", "full", "async"):
            raise ValueError(f"unknown engine {engine!r}")
        if num_clients is None:
            raise TypeError("from_strategy/strategy= requires num_clients")
        self.strategy = strategy
        self.cfg = strategy.federated_config(num_clients)
        self.schedule = strategy.sampling
        self.params = init_params
        self.eval_fn = eval_fn
        self.engine = engine
        self.scan_rounds = scan_rounds
        self._loss_fn = loss_fn
        self._key = jax.random.PRNGKey(seed)
        # AOT executables, keyed by (program, bucket, seg_len, input
        # avals); see federated.aot_compile.
        self._compiled: Dict[tuple, Any] = {}
        self._store_programs: Dict[int, Any] = {}  # bucket -> StoreRound
        # All per-client server state — EF residuals, the adaptive
        # samplers' norm EMAs (ones = "everyone looks equally important"
        # until data arrives, so round 1 ~ uniform), model versions —
        # lives in a ClientStateStore (DESIGN.md §11).  The default dense
        # backend reproduces the historical (M, …) arrays bit for bit; a
        # sharded store holds residuals only inside its retention window
        # and routes sync rounds through _run_store.
        self._adaptive = strategy.sampler.adaptive
        # FedDyn's per-client drift vector is a SECOND O(M × model) state
        # tree riding the same store as the residuals (DESIGN.md §12).
        self._uses_drift = self.cfg.client.objective.uses_drift
        if store is None:
            extra = {"drift": init_params} if self._uses_drift else None
            store = DenseStore(num_clients, init_params,
                               track_norms=self._adaptive,
                               extra_trees=extra)
        if store.num_clients != num_clients:
            raise ValueError(
                f"store was built for {store.num_clients} clients but the "
                f"server registers {num_clients}")
        if self._adaptive and store.norms is None:
            raise ValueError(
                f"strategy {strategy.name!r} uses an adaptive sampler; "
                "build the store with track_norms=True")
        if self._uses_drift and "drift" not in store.trees:
            raise ValueError(
                f"strategy {strategy.name!r} carries FedDyn drift state; "
                "build the store with extra_trees={'drift': init_params}")
        if engine == "full" and store.kind != "dense":
            raise ValueError(
                "engine='full' materializes every client's state per round "
                f"— incompatible with a {store.kind!r} store; use "
                "engine='cohort' or 'async'")
        self.store = store
        # Simulated-fleet traits (static per-client draws) for the hetero
        # round clock; None on the paper's ideal homogeneous fleet.
        self._traits = (strategy.hetero.client_traits(num_clients)
                        if strategy.hetero is not None else None)
        # Absolute round counter: run() continues from here, so a server
        # restored via restore_state resumes mid-run bit-identically.
        self._round = 0
        self._async = None
        if engine == "async":
            from repro.core.async_engine import AsyncRoundRunner
            self._async = AsyncRoundRunner(strategy, loss_fn, num_clients,
                                           store=self.store)
        self.history: List[RoundRecord] = []
        self._num_params = pytree_num_params(init_params)
        # Exact per-client-upload wire bytes: the codec's encode traced
        # shape-only over a delta template (same avals as params).
        self.client_upload_bytes = strategy.codec.wire_bytes(init_params)

    @classmethod
    def from_strategy(cls, strategy, loss_fn: Callable, init_params: PyTree,
                      num_clients: int, eval_fn: Optional[Callable] = None,
                      seed: int = 0, engine: str = "cohort",
                      scan_rounds: bool = True,
                      store: Optional[ClientStateStore] = None
                      ) -> "FederatedServer":
        """Build a server from one :class:`FedStrategy` — sampling, masking,
        wire codec, aggregator and client hyperparameters all come from the
        strategy record (e.g. ``strategy.get("fig5")``).  ``store`` picks
        the client-state backend (``repro.core.client_store``); None means
        a dense oracle store, reproducing the historical behaviour."""
        return cls(loss_fn, init_params=init_params, eval_fn=eval_fn,
                   seed=seed, engine=engine, scan_rounds=scan_rounds,
                   strategy=strategy, num_clients=num_clients, store=store)

    # ---- per-client state (delegated to the store) -----------------------
    @property
    def _residuals(self) -> PyTree:
        """Dense ``(M, …)`` view of the store's EF residuals.  On the
        dense backend this is the backing array itself (the in-program
        engines consume and reassign it); a sharded store materializes it
        on demand — test/debug only."""
        return self.store.residuals_dense()

    @_residuals.setter
    def _residuals(self, value: PyTree) -> None:
        self.store.set_dense(value)

    @property
    def _drift(self) -> PyTree:
        """Dense ``(M, …)`` view of the FedDyn drift tree (same caveats
        as :attr:`_residuals`)."""
        return self.store.dense_view("drift")

    @_drift.setter
    def _drift(self, value: PyTree) -> None:
        self.store.set_dense(value, tree="drift")

    @property
    def _norms(self) -> Optional[jnp.ndarray]:
        return self.store.norms

    @_norms.setter
    def _norms(self, value) -> None:
        if value is None and self.store.norms is None:
            return
        self.store.set_norms(value)

    # ---- engine dispatch -------------------------------------------------
    def _round_program(self, bucket: int, seg_len: int):
        """Build the (bucket, seg_len) round program (uncompiled) from the
        strategy — ``strategy.build_round`` threads the codec and
        aggregator into every form."""
        from repro.core.strategy import build_round
        M = self.cfg.num_clients
        if seg_len > 1:
            return build_round(self.strategy, self._loss_fn, M,
                               form="scan", cohort_size=bucket)
        if bucket >= M:
            return build_round(self.strategy, self._loss_fn, M, form="full")
        return build_round(self.strategy, self._loss_fn, M,
                           form="cohort", cohort_size=bucket)

    def _segments(self, rounds: int, eval_rounds, start: int = 0) -> List[tuple]:
        """Split start+1..start+rounds into (bucket, [t...]) segments:
        consecutive rounds sharing a cohort bucket, broken at eval rounds
        (the host needs Θ_t there).  engine="full" pins every bucket to the
        full population.  Bucket sizing is sampler-aware:
        ``ClientSampler.cohort_bucket`` upper-bounds the participant count
        its selection can emit (e.g. the threshold sampler's random arrival
        count gets a slack bucket)."""
        M = self.cfg.num_clients
        sampler = self.strategy.sampler
        plan = self.schedule.round_buckets(rounds, M, start=start)
        segments: List[tuple] = []
        for t, (m, _bucket) in zip(range(start + 1, start + rounds + 1), plan):
            bucket = sampler.cohort_bucket(self.schedule, m, M)
            b_eff = bucket if self.engine == "cohort" else M
            if (segments and self.scan_rounds
                    and segments[-1][0] == b_eff
                    and (t - 1) not in eval_rounds):
                segments[-1][1].append(t)
            else:
                segments.append((b_eff, [t]))
        return segments

    # ---- training loop ---------------------------------------------------
    def run(self, client_batches: PyTree, n_samples: np.ndarray,
            rounds: int, eval_every: int = 0,
            eval_data: Any = None) -> List[RoundRecord]:
        """Run ``rounds`` communication rounds, appending to ``history``.

        ``client_batches``: pytree with leading (num_clients, num_batches,
        B, ...) axes; ``n_samples``: (num_clients,) per-client dataset
        sizes; ``eval_every``: evaluate ``eval_fn(params, eval_data)``
        every that many rounds (and on the last).  Returns the full
        history list.  Rounds are numbered from the server's persistent
        round counter, so a run on a ``restore_state``-d server continues
        where the checkpoint left off.

        On a sharded store (any engine), ``client_batches`` may instead be
        a *provider* callable ``provider(ids) -> cohort_batches`` (leading
        axes ``(len(ids), num_batches, B, ...)``) so the full ``(M, …)``
        batch stack never has to exist either — the scaling benchmark's
        path to M = 10^6.
        """
        gamma = self.cfg.client.masking.gamma \
            if self.cfg.client.masking.mode != "none" else 1.0
        wire_bytes = self.client_upload_bytes
        n_samples = jnp.asarray(n_samples, jnp.float32)
        if callable(client_batches):
            if self.store.kind == "dense":
                raise ValueError(
                    "a client_batches provider callable requires a sharded "
                    "store (the dense engines close over the full batch "
                    "stack)")
            probe = client_batches(np.zeros((1,), np.int64))
            flops_per_client = local_update_flops(
                probe, self._num_params, self.cfg.client)
        else:
            flops_per_client = local_update_flops(
                client_batches, self._num_params, self.cfg.client)
        start = self._round

        eval_rounds = set()
        if eval_every and self.eval_fn is not None:
            eval_rounds = {t for t in range(start + 1, start + rounds + 1)
                           if t % eval_every == 0 or t == start + rounds}

        if self.engine == "async":
            return self._run_async(client_batches, n_samples, rounds,
                                   eval_rounds, eval_data, gamma, wire_bytes,
                                   flops_per_client)
        if self.store.kind != "dense":
            return self._run_store(client_batches, n_samples, rounds,
                                   eval_rounds, eval_data, gamma, wire_bytes,
                                   flops_per_client)

        # Host spans on the profiler's clock, beside the device ops:
        # ``fl.plan`` (the schedule's cohort sizes and buckets), then
        # ``fl.round`` per segment; inside it ``fl.launch`` (key split,
        # argument building, executable lookup, dispatch), ``fl.wait`` and
        # ``fl.readback`` (metric pulls, records, eval).
        with jax.profiler.TraceAnnotation("fl.plan"):
            segments = self._segments(rounds, eval_rounds, start)
        for bucket, ts in segments:
            seg_len = len(ts)
            with jax.profiler.TraceAnnotation("fl.round", bucket=bucket,
                                              rounds=seg_len):
                with jax.profiler.TraceAnnotation("fl.launch"):
                    metrics, compile_s, t0 = self._launch_segment(
                        bucket, ts, client_batches, n_samples)
                with jax.profiler.TraceAnnotation("fl.wait"):
                    jax.block_until_ready(self.params)
                wall = time.perf_counter() - t0
                with jax.profiler.TraceAnnotation("fl.readback"):
                    self._record_segment(bucket, ts, metrics, wall,
                                         compile_s, gamma, wire_bytes,
                                         flops_per_client, eval_rounds,
                                         eval_data)
            self._round = ts[-1]
        return self.history

    def _launch_segment(self, bucket: int, ts: List[int], client_batches,
                        n_samples):
        """Dispatch one segment's round program; the new state is stored
        on the server before the device finishes.  Returns the metrics
        (device arrays), the compile seconds (0 on a cache hit) and the
        clock reading just before the call."""
        seg_len = len(ts)
        subs = []
        for _ in ts:
            self._key, sub = jax.random.split(self._key)
            subs.append(sub)
        if seg_len > 1:
            t_arg = jnp.asarray(ts, jnp.float32)
            key_arg = jnp.stack(subs)
        else:
            t_arg = jnp.asarray(ts[0], jnp.float32)
            key_arg = subs[0]
        # Engine-wide state convention: (params, residuals[, drift]
        # [, norms]) — optional slots appear only when the strategy
        # carries that state, so historical programs are unchanged.
        state = [self.params, self._residuals]
        if self._uses_drift:
            state.append(self._drift)
        if self._adaptive:
            state.append(self._norms)
        args = (*state, client_batches, n_samples, t_arg, key_arg)
        compiled, compile_s = aot_compile(
            self._compiled, "round", bucket,
            lambda: self._round_program(bucket, seg_len), args, seg_len)
        t0 = time.perf_counter()
        out = compiled(*args)
        self.params, self._residuals = out[0], out[1]
        i = 2
        if self._uses_drift:
            self._drift = out[i]
            i += 1
        if self._adaptive:
            self._norms = out[i]
            i += 1
        return out[i], compile_s, t0

    def _record_segment(self, bucket, ts, metrics, wall, compile_s, gamma,
                        wire_bytes, flops_per_client, eval_rounds,
                        eval_data):
        """Pull one segment's metrics to the host and append a
        ``RoundRecord`` per round (with the simulated-fleet clock and the
        eval where they apply).  ``metrics`` leaves are stacked per round,
        or scalars for a one-round segment (the store loop's)."""
        seg_len = len(ts)
        num_sampled = np.atleast_1d(np.asarray(metrics["num_sampled"]))
        mean_loss = np.atleast_1d(np.asarray(metrics["mean_loss"]))
        quarantined = np.atleast_1d(np.asarray(metrics["quarantined"]))
        adversarial = None
        if "num_adversarial" in metrics:
            adversarial = np.atleast_1d(
                np.asarray(metrics["num_adversarial"]))
        if self._traits is not None:
            part_masks = np.atleast_2d(np.asarray(metrics["part_mask"]))
            arrived_masks = np.atleast_2d(
                np.asarray(metrics["arrived_mask"]))
        for i, t in enumerate(ts):
            m = float(num_sampled[i])
            rec = RoundRecord(
                round=t,
                num_sampled=int(m),
                mean_loss=float(mean_loss[i]),
                transport_units=m * gamma,
                transport_bytes=int(m) * wire_bytes,
                wall_s=wall / seg_len,
                compile_s=compile_s if i == 0 else 0.0,
                cohort_size=bucket,
                quarantined=int(quarantined[i]),
                adversarial=(int(adversarial[i])
                             if adversarial is not None else 0),
            )
            if self._traits is not None:
                sim = simulate_round(self._traits, part_masks[i],
                                     arrived_masks[i],
                                     float(flops_per_client), wire_bytes)
                rec.sim_round_s = sim["sim_round_s"]
                rec.straggler_s = sim["straggler_s"]
                rec.dropped = sim["dropped"]
            if t in eval_rounds and t == ts[-1]:
                rec.eval_metric = float(self.eval_fn(self.params, eval_data))
            self.history.append(rec)

    def _run_async(self, client_batches, n_samples, rounds, eval_rounds,
                   eval_data, gamma, wire_bytes, flops_per_client):
        """engine="async" round loop: one buffered round at a time via
        :class:`repro.core.async_engine.AsyncRoundRunner`, with the SAME
        per-round key-split sequence as the sync engines (bit-exactness in
        the degenerate case depends on it).  Transport counts every
        transmission the fleet attempted — retries and deadline-cut sends
        included — because those bytes crossed the uplink either way."""
        M = self.cfg.num_clients
        sampler = self.strategy.sampler
        # On a sharded store the runner gathers/commits residual rows and
        # norm EMAs through the store itself — never materialize the dense
        # (M, …) view here.
        sharded = self.store.kind != "dense"
        for _ in range(rounds):
            t = self._round + 1
            self._key, sub = jax.random.split(self._key)
            m = self.schedule.num_clients_host(t, M)
            bucket = sampler.cohort_bucket(self.schedule, m, M)
            t0 = time.perf_counter()
            res_in = None if sharded else self._residuals
            (self.params, res_out, norms_out,
             stats) = self._async.run_round(
                self.params, res_in, self._norms, client_batches,
                n_samples, t, sub, cohort_size=bucket,
                flops=float(flops_per_client), wire_bytes=wire_bytes)
            if not sharded:
                self._residuals = res_out
                self._norms = norms_out
            jax.block_until_ready(self.params)
            wall = max(0.0, time.perf_counter() - t0 - stats["compile_s"])
            rec = RoundRecord(
                round=t,
                num_sampled=stats["num_sampled"],
                mean_loss=stats["mean_loss"],
                transport_units=stats["sends"] * gamma,
                transport_bytes=stats["sends"] * wire_bytes,
                wall_s=wall,
                compile_s=stats["compile_s"],
                cohort_size=bucket,
                sim_round_s=stats["sim_round_s"],
                straggler_s=stats["straggler_s"],
                dropped=stats["dropped"],
                arrivals=stats["arrivals"],
                timeouts=stats["timeouts"],
                retries=stats["retries"],
                quarantined=stats["quarantined"],
                flushes=stats["flushes"],
                mean_staleness=stats["mean_staleness"],
                carried=stats.get("carried", 0),
                pending=stats.get("pending", 0),
                adversarial=stats["adversarial"],
            )
            if t in eval_rounds:
                rec.eval_metric = float(self.eval_fn(self.params, eval_data))
            self.history.append(rec)
            self._round = t
        return self.history

    # ---- store engine (sharded sync; DESIGN.md §11) ----------------------
    def _store_program(self, bucket: int):
        """The (cached) store-form round program for one cohort bucket."""
        prog = self._store_programs.get(bucket)
        if prog is None:
            from repro.core.strategy import build_round
            prog = build_round(self.strategy, self._loss_fn,
                               self.cfg.num_clients, form="store",
                               cohort_size=bucket)
            self._store_programs[bucket] = prog
        return prog

    def _run_store(self, client_batches, n_samples, rounds, eval_rounds,
                   eval_data, gamma, wire_bytes, flops_per_client):
        """Sync round loop through the client-state store: selection and
        the cohort-shaped barrier run as separate AOT programs, with the
        residual gather/scatter between them going through ``self.store``
        — the full ``(M, …)`` residual stack never exists.  Per-round key
        splits are identical to the in-program engines (bit-exactness of
        dense-vs-sharded runs depends on it)."""
        M = self.cfg.num_clients
        sampler = self.strategy.sampler
        store = self.store
        provider = client_batches if callable(client_batches) else None
        for _ in range(rounds):
            t = self._round + 1
            self._key, sub = jax.random.split(self._key)
            m = self.schedule.num_clients_host(t, M)
            bucket = sampler.cohort_bucket(self.schedule, m, M)
            prog = self._store_program(bucket)
            sample_key, mask_key, drop_key = _split_round_key(
                sub, prog.with_drop)
            t_arg = jnp.asarray(t, jnp.float32)
            norms = store.norms if prog.adaptive else None

            sel_args = (norms, n_samples, t_arg, sample_key)
            sel_fn, compile_s = aot_compile(self._compiled, "store-sel",
                                            bucket, lambda: prog.select,
                                            sel_args)
            t0 = time.perf_counter()
            part, weights, cohort_ids = sel_fn(*sel_args)
            ids_np = np.asarray(cohort_ids)
            cohort_res = store.gather(ids_np)
            cohort_drift = (store.gather(ids_np, tree="drift")
                            if prog.uses_drift else None)
            if provider is not None:
                cohort_batches = provider(ids_np)
            else:
                cohort_batches = jax.tree.map(
                    lambda x: jnp.take(x, cohort_ids, axis=0),
                    client_batches)
            gather_s = time.perf_counter() - t0

            body_args = (self.params, cohort_res, cohort_drift,
                         cohort_batches, cohort_ids, part, weights, norms,
                         mask_key, drop_key)
            body_fn, body_compile_s = aot_compile(
                self._compiled, "store-body", bucket, lambda: prog.body,
                body_args)
            compile_s += body_compile_s
            t0 = time.perf_counter()
            (self.params, new_rows, drift_rows, commit, norm_upd,
             metrics) = body_fn(*body_args)
            jax.block_until_ready(self.params)
            wall = gather_s + (time.perf_counter() - t0)

            part_np = np.asarray(part)
            # Θ_t went out to the true participants this round — the
            # version state cross-round staleness measures against.
            store.mark_dispatched(ids_np[part_np[ids_np] > 0], t)
            commit_np = np.asarray(commit)
            if prog.error_feedback:
                store.scatter(ids_np, new_rows, commit_np, t)
            if prog.uses_drift:
                store.scatter(ids_np, drift_rows, commit_np, t, tree="drift")
            if prog.adaptive:
                store.update_norms(ids_np, norm_upd)

            self._record_segment(bucket, [t], metrics, wall, compile_s,
                                 gamma, wire_bytes, flops_per_client,
                                 eval_rounds, eval_data)
            self._round = t
        return self.history

    # ---- checkpoint / resume --------------------------------------------
    def state(self) -> Dict[str, Any]:
        """The complete resumable training state as one pytree: global
        params, the server RNG key, and the store's per-client state (EF
        residuals — dense stack or sharded slot pool —, sampler norm EMAs,
        model versions).  The round counter rides in the checkpoint's
        ``extra`` manifest."""
        return {
            "key": self._key,
            "params": self.params,
            **self.store.state(),
        }

    def save_state(self, ckpt_dir: str) -> str:
        """Checkpoint :meth:`state` (atomically) at the current round.
        The manifest's ``extra`` records the round counter plus the
        population size and store backend, so a mismatched restore fails
        loudly before any state is touched."""
        from repro.checkpoint.checkpoint import save_checkpoint
        return save_checkpoint(ckpt_dir, self._round, self.state(),
                               extra={"round": self._round,
                                      "num_clients": self.cfg.num_clients,
                                      "store": self.store.kind})

    def restore_state(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        """Restore :meth:`state` from ``ckpt_dir`` (latest step unless
        pinned) and continue the round numbering where the checkpoint left
        off; the next ``run()`` resumes bit-identically to the run that
        wrote it.  Returns the restored step.

        Validates the checkpoint against this server BEFORE assigning
        anything: a checkpoint written for a different population size or
        store backend raises ``ValueError`` naming both values instead of
        silently loading mismatched per-client state."""
        from repro.checkpoint.checkpoint import (read_manifest,
                                                 restore_checkpoint)
        extra = read_manifest(ckpt_dir, step).get("extra", {})
        ckpt_m = extra.get("num_clients")
        if ckpt_m is not None and int(ckpt_m) != self.cfg.num_clients:
            raise ValueError(
                f"checkpoint was written for num_clients={int(ckpt_m)} but "
                f"this server registers num_clients={self.cfg.num_clients}")
        ckpt_store = extra.get("store")
        if ckpt_store is not None and ckpt_store != self.store.kind:
            raise ValueError(
                f"checkpoint holds a {ckpt_store!r} store but this server "
                f"owns a {self.store.kind!r} store")
        restored, step, extra = restore_checkpoint(ckpt_dir, self.state(),
                                                   step)
        self._key = jnp.asarray(restored.pop("key"))
        self.params = restored.pop("params")
        self.store.load_state(restored)
        self._round = int(extra.get("round", step))
        return step

    # ---- reporting ------------------------------------------------------
    def compiled_hlo(self) -> List[str]:
        """Optimized HLO text of every round program compiled so far — on a
        TPU, each compiled Pallas kernel shows up as a ``tpu_custom_call``."""
        return [c.as_text() for c in self._compiled.values()]

    def total_transport_units(self) -> float:
        """Cumulative client uploads in full-model units (Eq. 6 basis)."""
        return float(sum(r.transport_units for r in self.history))

    def total_transport_bytes(self) -> int:
        """Cumulative EXACT wire bytes across all recorded rounds."""
        return int(sum(r.transport_bytes for r in self.history))

    def summary(self) -> Dict[str, Any]:
        """Run-level roll-up of the history (loss, transport, timing; plus
        the simulated-fleet clock and drop counts when hetero is on)."""
        evals = [r.eval_metric for r in self.history if r.eval_metric is not None]
        out = {
            "rounds": len(self.history),
            "final_loss": self.history[-1].mean_loss if self.history else float("nan"),
            "final_eval": evals[-1] if evals else float("nan"),
            "transport_units": self.total_transport_units(),
            "transport_bytes": self.total_transport_bytes(),
            "transport_GB": self.total_transport_bytes() / 1e9,
            "num_params": self._num_params,
            "engine": self.engine,
            "strategy": self.strategy.name,
            "sampler": self.strategy.sampler.name,
            # wire accounting now comes from the codec, not an estimate
            "codec": self.strategy.codec.name,
            "client_upload_bytes": self.client_upload_bytes,
            "compile_s": float(sum(r.compile_s for r in self.history)),
            "steady_wall_s": float(sum(r.wall_s for r in self.history)),
            # decode-gate rejections, metered by every engine (§8/§9)
            "quarantined": int(sum(r.quarantined for r in self.history)),
        }
        attack = getattr(self.strategy, "attack", None)
        if attack is not None and attack.active:
            out["attack"] = f"{attack.kind}(f={attack.fraction})"
            out["adversarial_uploads"] = int(
                sum(r.adversarial for r in self.history))
        if self._traits is not None:
            out["hetero"] = self.strategy.hetero.profile
            out["sim_total_s"] = float(
                sum(r.sim_round_s for r in self.history))
            out["dropped_uploads"] = int(sum(r.dropped for r in self.history))
        if self.engine == "async":
            arrivals = int(sum(r.arrivals for r in self.history))
            out["sim_total_s"] = float(
                sum(r.sim_round_s for r in self.history))
            out["dropped_uploads"] = int(sum(r.dropped for r in self.history))
            out["arrivals"] = arrivals
            out["timeouts"] = int(sum(r.timeouts for r in self.history))
            out["retries"] = int(sum(r.retries for r in self.history))
            out["flushes"] = int(sum(r.flushes for r in self.history))
            # staleness averaged over APPLIED uploads, not over rounds
            out["mean_staleness"] = float(
                sum(r.mean_staleness * r.arrivals for r in self.history)
                / arrivals) if arrivals else 0.0
            out["carried"] = int(sum(r.carried for r in self.history))
        return out
