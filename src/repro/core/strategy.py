"""Composable federated strategies: sampling × masking × codec × aggregation.

A *scenario* used to be threaded through five call sites as loose kwargs
(``make_federated_round(loss_fn, schedule, masking_cfg, use_kernel, ...)``,
``FederatedServer.__init__`` re-took the same set, ``FedPodConfig``
duplicated it again).  A :class:`FedStrategy` makes the scenario *data*:
one frozen record composing four pluggable policies —

* ``sampling``    — a :class:`repro.core.sampling.SamplingSchedule`
  (static / dynamic c(t));
* ``masking``     — a :class:`MaskPolicy` (none / random / selective top-k,
  jnp-bisection or segmented-Pallas-kernel backend);
* ``codec``       — a :class:`repro.core.codecs.UploadCodec` (identity /
  sparse COO / int8 / chained), the REAL encode → wire → decode transform
  the round applies to every client upload, with exact ``wire_bytes()``;
* ``aggregator``  — an :class:`Aggregator` (weighted fedavg now; clipped
  fedavg as the first registry alternative, trimmed-mean et al. slot in
  the same way);
* ``sampler``     — a :class:`repro.core.sampling.ClientSampler` picking
  WHICH m_t clients (uniform / importance / threshold) with unbiased
  aggregation weights (DESIGN.md §5);
* ``hetero``      — an optional :class:`repro.core.hetero.HeteroModel`
  putting the round on a heterogeneous simulated fleet (per-client
  compute/latency/bandwidth/dropout; DESIGN.md §5);
* ``async_cfg``   — an optional :class:`repro.core.async_engine.AsyncConfig`
  switching the server to FedBuff-style asynchronous buffered aggregation
  with a failure model (deadlines, retry/backoff, upload quarantine;
  DESIGN.md §8) when it runs with ``engine="async"``;
* ``attack``      — an optional :class:`repro.core.attacks.AttackModel`
  making a fixed fraction of the fleet Byzantine: adversary uploads are
  perturbed at the decode boundary of every engine (DESIGN.md §9), the
  scenario the robust aggregators in ``repro.core.robust`` are built for.

plus the client-side hyperparameters (local epochs, lr, momentum, upload
semantics, error feedback).  ``build_round`` turns a strategy into the
oracle / cohort / scan round program; ``FederatedServer.from_strategy``
runs it end-to-end.  The string registry (``register`` / ``get``) holds the
paper presets — ``"fig3"``, ``"fig4"``, ``"fig5"``, ``"dense-baseline"``
(plus ``"fig5-int8"`` for the chained wire, ``"fig3-importance"`` for
norm-adaptive selection, and ``"hetero-dropout"`` for the flaky-fleet
scenario) — so a new scenario is a registry entry, not a plumbing change.

Every preset preserves the cohort-vs-oracle bit-exactness guarantee of
DESIGN.md §3.5 (property-tested per preset in tests/test_strategy.py): the
codec round-trip is deterministic per upload, so running only the sampled
cohort still reproduces the full-population oracle to the bit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.async_engine import AsyncConfig
from repro.core.attacks import AttackModel
from repro.core.client import ClientConfig
from repro.core.codecs import (BitmapCodec, ChainCodec, FusedSparseCodec,
                               IdentityCodec, Int8Codec, SparseCodec,
                               UploadCodec)
from repro.core.federated import (FederatedConfig, fedavg_aggregate,
                                  make_cohort_round, make_cohort_scan,
                                  make_federated_round, make_store_round)
from repro.core.hetero import HeteroModel
from repro.core.masking import MaskingConfig
from repro.core.objectives import LocalObjective
from repro.core.sampling import (ClientSampler, DynamicSampling,
                                 ImportanceSampler, SamplingSchedule,
                                 StaticSampling, UniformSampler)

PyTree = Any

__all__ = [
    "MaskPolicy",
    "Aggregator",
    "FEDAVG",
    "clipped_fedavg",
    "get_aggregator",
    "aggregator_names",
    "FedStrategy",
    "default_codec",
    "build_round",
    "register",
    "get",
    "names",
]


# ---------------------------------------------------------------------------
# mask policy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MaskPolicy:
    """Which entries of the client delta survive the upload.

    ``backend`` selects the selective-top-k implementation: ``"jnp"`` is
    the pure threshold-bisection path (DESIGN.md §3.1), ``"kernel"`` routes
    the whole pytree through the segmented Pallas subsystem (§3.4).
    """

    mode: str = "none"          # none | random | selective
    gamma: float = 1.0          # fraction KEPT (paper's masking rate)
    backend: str = "jnp"        # jnp | kernel
    min_leaf_size: int = 256
    bisect_iters: int = 24

    def __post_init__(self):
        if self.mode not in ("none", "random", "selective"):
            raise ValueError(f"unknown masking mode {self.mode!r}")
        if self.backend not in ("jnp", "kernel"):
            raise ValueError(f"unknown masking backend {self.backend!r}")
        if not 0.0 < self.gamma <= 1.0:
            raise ValueError(f"gamma must be in (0, 1], got {self.gamma}")

    @classmethod
    def none(cls) -> "MaskPolicy":
        """Dense uploads: every delta entry survives."""
        return cls()

    @classmethod
    def random(cls, gamma: float, **kw) -> "MaskPolicy":
        """Keep a random ``gamma`` fraction of each maskable leaf."""
        return cls(mode="random", gamma=gamma, **kw)

    @classmethod
    def selective(cls, gamma: float, backend: str = "jnp", **kw) -> "MaskPolicy":
        """Keep the top-``gamma`` fraction by magnitude (paper Alg. 4)."""
        return cls(mode="selective", gamma=gamma, backend=backend, **kw)

    @classmethod
    def from_masking_config(cls, cfg: MaskingConfig) -> "MaskPolicy":
        """Lift a legacy :class:`MaskingConfig` into a policy record."""
        return cls(mode=cfg.mode, gamma=cfg.gamma,
                   backend="kernel" if cfg.use_kernel else "jnp",
                   min_leaf_size=cfg.min_leaf_size,
                   bisect_iters=cfg.bisect_iters)

    def masking_config(self) -> MaskingConfig:
        """Lower the policy back to the client-side :class:`MaskingConfig`."""
        return MaskingConfig(gamma=self.gamma, mode=self.mode,
                             min_leaf_size=self.min_leaf_size,
                             bisect_iters=self.bisect_iters,
                             use_kernel=self.backend == "kernel")


# ---------------------------------------------------------------------------
# aggregators
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Aggregator:
    """Server-side combination rule over stacked client uploads.

    ``fn(global_params, uploads, weights, upload_semantics, normalize=True)
    -> params`` with a leading client axis on every ``uploads`` leaf.
    ``normalize=False`` means the sampler already folded its inverse
    selection probabilities into ``weights`` (Horvitz-Thompson), so the fn
    must use them as-is rather than re-normalizing to sum 1.  Legacy fns
    without the ``normalize`` parameter still work under self-normalizing
    samplers; pairing one with a Horvitz-Thompson sampler raises a
    ``TypeError`` at round-build time.  Must treat zero-weight rows as
    absent (the cohort/oracle equivalence relies on the oracle's extra
    zero-weight clients being no-ops).

    ``ht_compatible=False`` declares the rule unable to honour HT weights
    at all (Krum-family: candidate selection ignores weight magnitudes);
    building a round that pairs such an aggregator with an HT sampler
    raises a ``TypeError`` (``repro.core.federated._resolve_aggregator``).
    """

    name: str
    fn: Callable[..., PyTree]
    ht_compatible: bool = True


FEDAVG = Aggregator("fedavg", fedavg_aggregate)


def clipped_fedavg(max_norm: float) -> Aggregator:
    """FedAvg over per-client norm-clipped uploads (robustness knob).

    Zero uploads stay zero after clipping, so the cohort-vs-oracle
    bit-exactness guarantee survives: the oracle's zero-weight rows clip to
    themselves and then drop out of the weighted sum exactly as before.
    """
    if max_norm <= 0.0:
        raise ValueError(
            f"clipped_fedavg: max_norm must be > 0, got {max_norm}")

    def agg(global_params, uploads, weights, upload_semantics,
            normalize=True):
        sq = sum(jnp.sum(jnp.square(u), axis=tuple(range(1, u.ndim)))
                 for u in jax.tree_util.tree_leaves(uploads))
        norm = jnp.sqrt(sq)
        factor = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        clipped = jax.tree_util.tree_map(
            lambda u: u * factor.reshape((-1,) + (1,) * (u.ndim - 1)),
            uploads)
        return fedavg_aggregate(global_params, clipped, weights,
                                upload_semantics, normalize=normalize)

    return Aggregator(f"clipped_fedavg({max_norm})", agg)


# Imported AFTER Aggregator is defined: robust.py builds Aggregator
# records lazily via this module, so the import must not run at the top.
from repro.core import robust as _robust  # noqa: E402

_AGGREGATORS: Dict[str, Callable[..., Aggregator]] = {
    "fedavg": lambda: FEDAVG,
    "clipped_fedavg": clipped_fedavg,
    "coordinate_median": _robust.coordinate_median,
    "trimmed_mean": _robust.trimmed_mean,
    "krum": _robust.krum,
    "multi_krum": _robust.multi_krum,
    "norm_filter": _robust.norm_filter,
}


def get_aggregator(name: str, *args, **kwargs) -> Aggregator:
    """Build a registered aggregator by factory name (knobs as args:
    ``get_aggregator("trimmed_mean", 0.2)``)."""
    try:
        factory = _AGGREGATORS[name]
    except KeyError:
        raise KeyError(
            f"unknown aggregator {name!r}; registered: "
            f"{', '.join(aggregator_names())}") from None
    return factory(*args, **kwargs)


def aggregator_names() -> Tuple[str, ...]:
    """Sorted factory names accepted by :func:`get_aggregator`."""
    return tuple(sorted(_AGGREGATORS))


# ---------------------------------------------------------------------------
# the strategy record
# ---------------------------------------------------------------------------
def default_codec(masking: MaskPolicy, quantized: bool = False,
                  backend: str = "jnp", wire: str = "coo") -> UploadCodec:
    """The wire format a mask policy implies: dense uploads ship identity,
    masked uploads ship sparse COO sized to gamma; ``quantized`` chains
    int8 on the value payload.

    ``backend``/``wire`` select the codec axis (DESIGN.md §10):
    ``backend="jnp"`` picks the reference codecs (``SparseCodec`` for
    ``wire="coo"``, ``BitmapCodec`` for ``wire="bitmap"``, int8 chained on
    top when ``quantized``); ``backend="fused"`` picks the kernel-backed
    :class:`FusedSparseCodec`, which emits the same wire (bytes and decoded
    values) from one fused Pallas sweep.
    """
    if backend not in ("jnp", "fused"):
        raise ValueError(f"unknown codec backend {backend!r}")
    if wire not in ("coo", "bitmap"):
        raise ValueError(f"unknown wire format {wire!r}")
    if masking.mode == "none" or masking.gamma >= 1.0:
        base: UploadCodec = IdentityCodec()
        return ChainCodec((base, Int8Codec())) if quantized else base
    if backend == "fused":
        return FusedSparseCodec(gamma=masking.gamma,
                                min_leaf_size=masking.min_leaf_size,
                                quantized=quantized, wire=wire)
    if wire == "bitmap":
        base = BitmapCodec(gamma=masking.gamma,
                           min_leaf_size=masking.min_leaf_size)
    else:
        base = SparseCodec(gamma=masking.gamma,
                           min_leaf_size=masking.min_leaf_size)
    if quantized:
        return ChainCodec((base, Int8Codec()))
    return base


@dataclasses.dataclass(frozen=True)
class FedStrategy:
    """One federated-learning scenario as data (see module docstring)."""

    name: str
    sampling: SamplingSchedule
    masking: MaskPolicy = MaskPolicy()
    codec: UploadCodec = IdentityCodec()
    aggregator: Aggregator = FEDAVG
    sampler: ClientSampler = UniformSampler()
    hetero: HeteroModel | None = None
    async_cfg: AsyncConfig | None = None
    attack: AttackModel | None = None
    local_epochs: int = 1
    learning_rate: float = 0.05
    momentum: float = 0.0
    upload: str = "delta"       # delta | zero (Alg. 4 literal)
    error_feedback: bool = False
    objective: LocalObjective = LocalObjective()

    # ---- derived configs -------------------------------------------------
    def client_config(self) -> ClientConfig:
        """The per-client hyperparameter record this strategy implies."""
        return ClientConfig(local_epochs=self.local_epochs,
                            learning_rate=self.learning_rate,
                            momentum=self.momentum,
                            masking=self.masking.masking_config(),
                            upload=self.upload,
                            objective=self.objective)

    def federated_config(self, num_clients: int) -> FederatedConfig:
        """The population-level round config for ``num_clients`` clients."""
        return FederatedConfig(num_clients=num_clients,
                               client=self.client_config(),
                               error_feedback=self.error_feedback)

    # ---- functional updates ---------------------------------------------
    def replace(self, **overrides) -> "FedStrategy":
        """Functional field update (frozen-record ``dataclasses.replace``)."""
        return dataclasses.replace(self, **overrides)

    def with_masking(self, masking: MaskPolicy, **overrides) -> "FedStrategy":
        """Replace the mask policy AND re-derive a consistent codec (COO
        slot counts track gamma), preserving int8 chaining and the
        codec backend/wire axis of the current codec.  Pass ``codec=``
        explicitly to opt out."""
        if "codec" not in overrides:
            overrides["codec"] = default_codec(
                masking, quantized=_quantizes(self.codec),
                backend=_codec_backend(self.codec),
                wire=_codec_wire(self.codec))
        return dataclasses.replace(self, masking=masking, **overrides)

    @classmethod
    def from_components(cls, name: str, sampling: SamplingSchedule,
                        masking: MaskingConfig | MaskPolicy | None = None,
                        **overrides) -> "FedStrategy":
        """Build a strategy from the legacy (schedule, MaskingConfig) pair,
        deriving the matching codec — the shim behind the deprecated
        ``FederatedServer`` kwargs path and the benchmark helpers."""
        if masking is None:
            masking = MaskPolicy.none()
        elif isinstance(masking, MaskingConfig):
            masking = MaskPolicy.from_masking_config(masking)
        if "codec" not in overrides:
            overrides["codec"] = default_codec(masking)
        return cls(name=name, sampling=sampling, masking=masking, **overrides)


def _quantizes(codec: UploadCodec) -> bool:
    if isinstance(codec, Int8Codec):
        return True
    if isinstance(codec, FusedSparseCodec):
        return codec.quantized
    if isinstance(codec, ChainCodec):
        return any(_quantizes(s) for s in codec.stages)
    return False


def _codec_backend(codec: UploadCodec) -> str:
    """The ``default_codec`` backend axis a codec sits on."""
    if isinstance(codec, FusedSparseCodec):
        return "fused"
    if isinstance(codec, ChainCodec):
        if any(_codec_backend(s) == "fused" for s in codec.stages):
            return "fused"
    return "jnp"


def _codec_wire(codec: UploadCodec) -> str:
    """The ``default_codec`` wire axis a codec sits on (coo | bitmap)."""
    if isinstance(codec, BitmapCodec):
        return "bitmap"
    if isinstance(codec, FusedSparseCodec):
        return codec.wire
    if isinstance(codec, ChainCodec):
        if any(_codec_wire(s) == "bitmap" for s in codec.stages):
            return "bitmap"
    return "coo"


# ---------------------------------------------------------------------------
# round construction: one object -> the engine
# ---------------------------------------------------------------------------
def build_round(strategy: FedStrategy, loss_fn: Callable, num_clients: int,
                form: str = "full", cohort_size: int | None = None):
    """Build the round program a strategy describes.

    ``form``: ``"full"`` — the all-clients vmap oracle; ``"cohort"`` — the
    bucketed cohort engine (requires ``cohort_size``); ``"scan"`` — the
    lax.scan-over-rounds fast path (requires ``cohort_size``; a
    ``cohort_size == num_clients`` scan wraps the oracle); ``"store"`` —
    the round split at the client-state-store boundary (requires
    ``cohort_size``; returns a ``repro.core.federated.StoreRound`` whose
    residual gather/scatter run OUTSIDE the program, through a
    ``repro.core.client_store.ClientStateStore``).  The strategy's codec,
    aggregator, client sampler and hetero model are threaded into the
    round body, so every form runs the same math.  When
    ``strategy.sampler.adaptive`` the returned program takes/returns an
    extra ``norms`` state vector after ``residuals`` (see
    ``repro.core.federated.make_federated_round``).
    """
    if form not in ("full", "cohort", "scan", "store"):
        raise ValueError(f"unknown round form {form!r}")
    cfg = strategy.federated_config(num_clients)
    kw = dict(codec=strategy.codec, aggregator=strategy.aggregator,
              sampler=strategy.sampler, hetero=strategy.hetero,
              attack=strategy.attack)
    if form == "full":
        return make_federated_round(loss_fn, strategy.sampling, cfg, **kw)
    if cohort_size is None:
        raise ValueError(f"form={form!r} requires cohort_size")
    if form == "cohort":
        return make_cohort_round(loss_fn, strategy.sampling, cfg,
                                 cohort_size, **kw)
    if form == "store":
        return make_store_round(loss_fn, strategy.sampling, cfg,
                                cohort_size, **kw)
    return make_cohort_scan(loss_fn, strategy.sampling, cfg,
                            cohort_size, **kw)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, FedStrategy] = {}


def register(strategy: FedStrategy, overwrite: bool = False) -> FedStrategy:
    """Add a strategy to the registry under its ``name`` (and return it)."""
    if not overwrite and strategy.name in _REGISTRY:
        raise ValueError(f"strategy {strategy.name!r} already registered")
    _REGISTRY[strategy.name] = strategy
    return strategy


def names() -> Tuple[str, ...]:
    """Sorted names of every registered strategy preset."""
    return tuple(sorted(_REGISTRY))


def get(name: str, **overrides) -> FedStrategy:
    """Fetch a registered strategy, optionally specialized via field
    overrides.  Overriding ``masking`` without an explicit ``codec``
    re-derives the codec so COO slot counts stay consistent with gamma."""
    try:
        base = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown strategy {name!r}; registered: {', '.join(names())}"
        ) from None
    if "masking" in overrides and "codec" not in overrides:
        masking = overrides.pop("masking")
        return base.with_masking(masking, **overrides)
    if overrides:
        return dataclasses.replace(base, **overrides)
    return base


# ---- paper presets --------------------------------------------------------
# "dense-baseline": Alg. 1 — full participation, dense uploads.
register(FedStrategy(
    name="dense-baseline",
    sampling=StaticSampling(initial_rate=1.0, min_clients=2)))

# "fig3": dynamic sampling alone (Alg. 3, beta = 0.1), dense uploads.
register(FedStrategy(
    name="fig3",
    sampling=DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2)))

# "fig4": selective masking alone (Alg. 4) at the paper's gamma = 0.1,
# sparse COO wire.
register(FedStrategy.from_components(
    "fig4", StaticSampling(initial_rate=1.0, min_clients=2),
    MaskPolicy.selective(0.1)))

# "fig5": both levers combined (Alg. 3 + Alg. 4) at the Fig. 5 operating
# point (beta = 0.1, gamma = 0.5), sparse COO wire.
register(FedStrategy.from_components(
    "fig5", DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2),
    MaskPolicy.selective(0.5)))

# "fig5-int8": beyond-paper — fig5 with the COO value payload int8-quantised
# (4 -> 1 bytes/kept value on the wire; lossy, error <= scale/2 per entry).
register(get("fig5").with_masking(
    MaskPolicy.selective(0.5),
    codec=ChainCodec((SparseCodec(gamma=0.5), Int8Codec())),
    name="fig5-int8"))

# "fig5-fused": fig5's operating point on the kernel-backed wire path
# (DESIGN.md §10) — the COO payload is emitted by one fused Pallas sweep
# (FusedSparseCodec) instead of the jnp codec's three re-reads; wire bytes
# and decoded values are identical to fig5's, so the cohort==oracle
# bit-exactness discipline extends to the fused backend.
register(get("fig5").replace(
    name="fig5-fused",
    codec=default_codec(MaskPolicy.selective(0.5), backend="fused")))

# "fig5-fused-int8": the fused wire path with int8 values quantised IN the
# same sweep (the scale rides the stats sweep) — byte-identical to
# fig5-int8's ChainCodec((Sparse, Int8)) wire.
register(get("fig5").replace(
    name="fig5-fused-int8",
    codec=default_codec(MaskPolicy.selective(0.5), quantized=True,
                        backend="fused")))

# "fig5-bitmap": fig5 shipped over the 1-bit/coord membership bitmap wire —
# at gamma = 0.5, far above the 1/32 density crossover, bitmap membership
# costs n/8 bytes where COO indices cost 4*k = 2n (DESIGN.md §10).
register(get("fig5").replace(
    name="fig5-bitmap",
    codec=default_codec(MaskPolicy.selective(0.5), wire="bitmap")))

# "fig3-importance": beyond-paper — fig3's dynamic c(t) schedule, but the
# m_t clients are CHOSEN by tracked update-norm importance with unbiased
# Horvitz-Thompson reweighting (Optimal-Client-Sampling style, DESIGN.md
# §5) instead of uniformly.
register(FedStrategy(
    name="fig3-importance",
    sampling=DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2),
    sampler=ImportanceSampler()))

# "hetero-dropout": beyond-paper — full-participation dense rounds on the
# flaky-mobile fleet: lognormal compute/latency/uplink spread and 20% of
# uploads lost, metered as sim_round_s / dropped in the server records.
register(FedStrategy(
    name="hetero-dropout",
    sampling=StaticSampling(initial_rate=1.0, min_clients=2),
    hetero=HeteroModel(profile="flaky-mobile")))

# "async-mobile": beyond-paper — fig3's dynamic c(t) on the mobile fleet,
# aggregated asynchronously (DESIGN.md §8): flush every K = m_t/2 arrivals
# with the FedBuff staleness discount, cut the round at the 90th arrival
# percentile, retry lost uploads twice with backoff.
register(FedStrategy(
    name="async-mobile",
    sampling=DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2),
    hetero=HeteroModel(profile="mobile"),
    async_cfg=AsyncConfig(buffer_frac=0.5, staleness_beta=0.5,
                          deadline_quantile=0.9, max_retries=2,
                          backoff_s=0.5, jitter_sigma=0.25)))

# "async-crossround": beyond-paper — async-mobile with a HARSH deadline
# (median arrival) and cross-round staleness (DESIGN.md §11): uploads cut
# at the deadline stay in flight and land in a later round, discounted by
# w/(1+s)^beta where s counts ROUNDS since the client pulled Θ, expiring
# past s = 3.  Requires a ClientStateStore (any backend) for the
# per-client model-version vector.
register(FedStrategy(
    name="async-crossround",
    sampling=DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2),
    hetero=HeteroModel(profile="mobile"),
    async_cfg=AsyncConfig(buffer_frac=0.5, staleness_beta=0.5,
                          deadline_quantile=0.5, max_retries=2,
                          backoff_s=0.5, jitter_sigma=0.25,
                          max_round_stale=3)))

# "async-flaky": the same async engine on the flaky-mobile fleet with an
# aggressive deadline (75th percentile) and a deeper retry budget — the
# chaos scenario the quarantine/timeout accounting is sized for.
register(FedStrategy(
    name="async-flaky",
    sampling=DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=2),
    hetero=HeteroModel(profile="flaky-mobile"),
    async_cfg=AsyncConfig(buffer_frac=0.5, staleness_beta=0.5,
                          deadline_quantile=0.75, max_retries=3,
                          backoff_s=0.5, jitter_sigma=0.25)))

# ---- local-objective presets (DESIGN.md §12) ------------------------------
# "fig5-prox": fig5's operating point with the FedProx proximal term
# (mu = 0.1): local loss L(w) + (mu/2)·||w − Θ_t||², damping client drift
# under heterogeneous data while leaving the wire path untouched.
register(get("fig5").replace(
    name="fig5-prox",
    objective=LocalObjective.prox(0.1)))

# "fig5-dyn": fig5 under FedDyn (alpha = 0.1): local loss
# L(w) − ⟨h_k, w⟩ + (alpha/2)·||w − Θ_t||² with the per-client drift
# vector h_k ← h_k − alpha·delta living in the client-state store
# (extra tree "drift"; DESIGN.md §12), updated on the HONEST pre-mask
# delta so masking never corrupts the drift dynamics.
register(get("fig5").replace(
    name="fig5-dyn",
    objective=LocalObjective.dyn(0.1)))

# "noniid-dyn": the non-IID flagship — fig5-dyn with importance-sampled
# client selection (norm-tracked, Horvitz-Thompson reweighted), the
# operating point benchmarks/noniid.py sweeps over Dirichlet partitions.
register(get("fig5-dyn").replace(
    name="noniid-dyn",
    sampler=ImportanceSampler()))

# ---- Byzantine-robustness presets (DESIGN.md §9) --------------------------
# All three run fig5's sparse operating point (beta = 0.1, gamma = 0.5, COO
# wire) with a deeper sampling floor: min_clients = 5 keeps every cohort an
# honest majority at f = 0.3 (late rounds of min_clients = 2 would hand a
# 30% fleet a coin-flip cohort majority, and Krum needs n >= f + 3
# candidates to score neighbours at all).
_ROBUST_SAMPLING = DynamicSampling(initial_rate=1.0, beta=0.1, min_clients=5)
# Amplified sign-flip: at strength = 4 and f = 0.3 the FedAvg mean is
# 0.7·u - 1.2·u = -0.5·u — an ascent direction, so plain averaging
# demonstrably diverges while the robust rules hold (benchmarks/robust_agg).
_SIGNFLIP = AttackModel(kind="sign_flip", fraction=0.3, strength=4.0)

# "byzantine-signflip": the attacked baseline — fig5 sparse uploads, 30%
# amplified sign-flip adversaries, PLAIN fedavg.  The control every robust
# preset is measured against.
register(get("fig5").replace(
    name="byzantine-signflip",
    sampling=_ROBUST_SAMPLING,
    attack=_SIGNFLIP))

# "robust-median": the same attacked fleet aggregated by the coordinate-wise
# weighted median (breakdown point 1/2 — f = 0.3 sign-flip cannot move it).
register(get("byzantine-signflip").replace(
    name="robust-median",
    aggregator=_robust.coordinate_median()))

# "robust-krum": the same attacked fleet under multi-Krum (f = 2 suspected
# Byzantine rows, average the m = 2 most central candidates) — the
# whole-vector geometric defence, immune to the median's per-coordinate
# sparse-support caveat (§9.4).
register(get("byzantine-signflip").replace(
    name="robust-krum",
    aggregator=_robust.multi_krum(f=2, m=2)))
