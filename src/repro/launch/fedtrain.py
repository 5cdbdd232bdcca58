"""The paper's federated round at pod scale (DESIGN.md §3.3/§6).

Mapping (cross-silo FL on a TPU pod):

* **Clients = data-axis slices.**  Single pod: the 16-wide "data" axis is the
  client axis (16 clients, each model-sharded 16-way over "model").
  Multi-pod: the "pod" axis is the client axis (2 silos), and each client's
  model shards over ("data","model") = 256 chips — this is how a 72B/400B
  client fits (a 400B client cannot live on 16 chips; a silo of 256 can).
* **Upload = masked weighted reduction.**  The paper's TCP upload becomes the
  cross-client weighted sum; selective masking runs on every client's delta
  *before* the reduction.  Everything is expressed as jnp over a
  client-leading axis under ``jax.jit`` — the SPMD partitioner emits the
  all-reduces over "model" (distributed threshold counts) and the
  reduce/all-gather over the client axis (the aggregation) that a hand-rolled
  shard_map would contain.
* **Distributed threshold top-k.**  The bisection counts are sum-reductions,
  so they work transparently on model-sharded leaves — each client finds the
  *global* per-layer threshold of its delta without gathering it.

Participation (dynamic sampling, Alg. 3) enters as a 0/1 weight vector
computed on the host from the schedule — shapes stay static.  Non-uniform
client samplers (DESIGN.md §5) reuse the same plumbing: run
``ClientSampler.select`` eagerly on the host (it is plain (M,)-shaped jnp)
and pass the returned *weights* as the participation vector with
``FedPodConfig.normalize=False`` — the round then uses them as the final
Horvitz-Thompson aggregation coefficients instead of re-normalizing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.core.codecs import roundtrip_stacked, with_axis0_slices
from repro.launch import shardings as sh
from repro.launch import steps as steps_lib
from repro.models import transformer as tr

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FedPodConfig:
    """Pod-round configuration.  Prefer :meth:`from_strategy` — one
    ``repro.core.strategy.FedStrategy`` supplies masking, codec, and client
    hyperparameters; the loose kwargs remain for scripts that predate the
    strategy API."""

    num_clients: int
    local_steps: int = 2          # local SGD steps per round (E epochs)
    learning_rate: float = 0.01
    gamma: float = 0.1            # fraction of params kept (paper default)
    masking: str = "selective"    # selective | random | none
    bisect_iters: int = 16
    min_leaf_size: int = 256
    # Route selective masking through the segmented Pallas subsystem
    # (ops.topk_mask_pytree): ~4 HBM sweeps per client for the WHOLE model
    # instead of O(L * iters) — off by default because the pure-jnp bisection
    # below is what the SPMD partitioner auto-shards over "model".
    use_kernel: bool = False
    # Wire codec (repro.core.codecs.UploadCodec): every client's masked
    # delta is round-tripped through its encode -> wire pytree -> decode
    # INSIDE the shard, so what enters the cross-client psum is exactly
    # what survived the wire.  None = dense (identity) upload.
    codec: Any = None
    # True (default): the participation vector is a 0/1 mask, weighted by
    # n_samples and re-normalized to sum 1 (self-normalized FedAvg).
    # False: the participation vector already IS the final aggregation
    # weights (a non-uniform ClientSampler's Horvitz-Thompson coefficients,
    # computed host-side) — used as given, n_samples ignored.
    normalize: bool = True

    @classmethod
    def from_strategy(cls, strategy, num_clients: int,
                      local_steps: int = 2) -> "FedPodConfig":
        """Collapse a FedStrategy onto the pod round: mask policy, codec,
        learning rate and the sampler's weight semantics come from the
        strategy record.  Sparse codec stages are re-budgeted to the pod
        masks' per-first-axis-slice top-k granularity
        (``with_axis0_slices``) so the wire never truncates a
        within-budget upload."""
        mp = strategy.masking
        return cls(num_clients=num_clients, local_steps=local_steps,
                   learning_rate=strategy.learning_rate, gamma=mp.gamma,
                   masking=mp.mode, bisect_iters=mp.bisect_iters,
                   min_leaf_size=mp.min_leaf_size,
                   use_kernel=mp.backend == "kernel",
                   codec=with_axis0_slices(strategy.codec),
                   normalize=strategy.sampler.normalize)


def _threshold_mask(delta: jax.Array, gamma: float, iters: int) -> jax.Array:
    """Vectorised threshold-bisection top-|delta| mask over the LAST
    ndim-leading dims; works on (C, G, ...) stacks (per client, per layer —
    Alg. 4 line 9's per-layer loop).  Pure sums/compares: auto-shardable."""
    lead = delta.shape[:2] if delta.ndim > 2 else delta.shape[:1]
    flat = delta.reshape(lead + (-1,)).astype(jnp.float32)
    n = flat.shape[-1]
    k = jnp.asarray(max(1, int(round(gamma * n))), jnp.int32)
    mag = jnp.abs(flat)
    hi = jnp.max(mag, axis=-1, keepdims=True) + 1e-12
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum(mag >= mid, axis=-1, keepdims=True)
        lo = jnp.where(count > k, mid, lo)
        hi = jnp.where(count > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    keep = (mag >= hi).astype(delta.dtype)
    return (flat.astype(delta.dtype) * keep).reshape(delta.shape)


def _random_mask(key: jax.Array, delta: jax.Array, gamma: float) -> jax.Array:
    """Exact-count random mask per last-dims block, matching
    ``_threshold_mask``'s granularity (per first-axis slice for stacked
    leaves).  Exact counts — not Bernoulli — so every upload fits the
    sparse wire's ``max(1, round(gamma * slice))`` slot budget instead of
    overflowing it on roughly half the draws."""
    lead = delta.shape[:2] if delta.ndim > 2 else delta.shape[:1]
    flat = delta.reshape(lead + (-1,))
    n = flat.shape[-1]
    k = max(1, int(round(gamma * n)))
    scores = jax.random.uniform(key, flat.shape).reshape(-1, n)
    # Single top_k pass per slice (as in core.masking.random_mask), not a
    # double-argsort ranking.
    _, idx = jax.lax.top_k(-scores, k)
    rows = jnp.arange(scores.shape[0])[:, None]
    keep = jnp.zeros(scores.shape, delta.dtype).at[rows, idx].set(1)
    return (flat * keep.reshape(flat.shape)).reshape(delta.shape)


def mask_deltas(key: jax.Array, deltas: PyTree, cfg: FedPodConfig) -> PyTree:
    """deltas: client-stacked pytree (leading C axis per leaf)."""
    if cfg.masking == "none" or cfg.gamma >= 1.0:
        return deltas
    if cfg.masking == "selective" and cfg.use_kernel:
        from repro.kernels import ops as kops

        def one_client(tree):
            # Match _threshold_mask's granularity exactly: leaves big enough
            # to mask (same per-client min_leaf_size gate) select top-k per
            # FIRST-axis slice for ndim >= 2 leaves (Alg. 4's per-layer loop
            # on stacked/layered arrays), per whole leaf for vectors.  Each
            # slice becomes its own segment of ONE packed sweep set.
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            out = list(leaves)
            segments, layout = [], []
            for i, leaf in enumerate(leaves):
                if leaf.size < cfg.min_leaf_size:
                    continue
                if leaf.ndim >= 2:
                    layout.append((i, leaf.shape[0]))
                    segments.extend(list(leaf))
                else:
                    layout.append((i, 0))
                    segments.append(leaf)
            if segments:
                from repro.core.masking import _refine_sweeps_for
                masked = kops.topk_mask_pytree(
                    tuple(segments), cfg.gamma, min_leaf_size=0,
                    refine_sweeps=_refine_sweeps_for(cfg.bisect_iters))
                pos = 0
                for i, g in layout:
                    if g:
                        out[i] = jnp.stack(masked[pos:pos + g])
                        pos += g
                    else:
                        out[i] = masked[pos]
                        pos += 1
            return jax.tree_util.tree_unflatten(treedef, out)

        # One segmented whole-model sweep set per client (leaf-count
        # independent); lax.map keeps a single kernel trace for all clients.
        return jax.lax.map(one_client, deltas)
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, lk in zip(leaves, keys):
        per_client = leaf.size // leaf.shape[0]
        if per_client < cfg.min_leaf_size:
            out.append(leaf)
        elif cfg.masking == "random":
            out.append(_random_mask(lk, leaf, cfg.gamma))
        else:
            out.append(_threshold_mask(leaf, cfg.gamma, cfg.bisect_iters))
    return jax.tree_util.tree_unflatten(treedef, out)


def _make_local_update(arch: ArchConfig, cfg: FedPodConfig,
                       hints=None) -> Callable:
    """E local SGD steps on one client's batch: ``(delta, mean_loss)``.
    Shared by the full-population and cohort pod rounds — the cohort engine
    must be a pure execution optimization, so there is exactly one
    definition of the client-side math."""
    def loss_fn(params, batch):
        return tr.lm_loss(params, arch, batch, hints=hints)

    def local_update(params, client_batch):
        def sgd_step(p, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            p = jax.tree.map(
                lambda x, g: (x - cfg.learning_rate * g).astype(x.dtype),
                p, grads)
            return p, loss
        local, losses = jax.lax.scan(sgd_step, params, client_batch)
        delta = jax.tree.map(lambda a, b: a - b, local, params)
        return delta, jnp.mean(losses)

    return local_update


def _weighted_upload(w: jax.Array, masked: PyTree) -> PyTree:
    """Client-axis weighted reduction of masked deltas.

    §Perf hillclimb 3: ship the masked deltas in bf16 — the upload
    (cross-client reduction) halves; the paper already quantises uploads
    ("compressed when uploaded", §3.2.1), bf16 is milder than its
    1-bit/ternary citations.  Accumulate in f32."""
    return jax.tree.map(
        lambda d: jnp.tensordot(w.astype(jnp.bfloat16),
                                d.astype(jnp.bfloat16), axes=(0, 0),
                                preferred_element_type=jnp.float32),
        masked)


def make_fed_round(arch: ArchConfig, cfg: FedPodConfig, hints=None) -> Callable:
    """Returns ``round(params, batches, n_samples, participation, key)``.

    batches: pytree with leading (C, local_steps, ...) axes.
    """
    local_update = _make_local_update(arch, cfg, hints=hints)

    def fed_round(params, batches, n_samples, participation, key):
        deltas, losses = jax.vmap(
            lambda b: local_update(params, b))(batches)
        masked = mask_deltas(key, deltas, cfg)
        # Each client's upload crosses the wire: encode -> wire pytree ->
        # decode through the strategy codec before the weighted reduction.
        masked = roundtrip_stacked(cfg.codec, masked)
        if cfg.normalize:
            w = participation * n_samples
            w = w / jnp.maximum(jnp.sum(w), 1e-12)
        else:
            w = participation          # pre-weighted (sampler coefficients)
        agg = _weighted_upload(w, masked)
        new_params = jax.tree.map(
            lambda p, a: (p + a.astype(p.dtype)), params, agg)
        active = (participation > 0).astype(jnp.float32)
        metrics = {
            "mean_loss": jnp.sum(losses * active)
            / jnp.maximum(jnp.sum(active), 1.0),
            "num_sampled": jnp.sum(active),
        }
        return new_params, metrics

    return fed_round


# ---------------------------------------------------------------------------
# cohort execution engine, pod form (DESIGN.md §3.5)
# ---------------------------------------------------------------------------
def make_cohort_fed_round(arch: ArchConfig, cfg: FedPodConfig,
                          cohort_size: int, mesh, client_axis: str = None,
                          hints=None) -> Callable:
    """Cohort-engine form of ``make_fed_round``: instead of running all
    ``cfg.num_clients`` registered clients and zero-weighting
    non-participants, gather only the sampled cohort (host-chosen ids,
    padded to the static ``cohort_size`` bucket) and ``shard_map`` the
    cohort axis over ``client_axis`` of ``mesh`` — each device runs
    ``cohort_size // mesh.shape[client_axis]`` clients and the upload is a
    per-device weighted partial sum followed by one psum.

    Returns ``round(params, batches, n_samples, cohort_ids, valid, key)``
    where ``batches`` has the full (C, local_steps, ...) registered-client
    leading axes, ``cohort_ids`` is int32 (cohort_size,) and ``valid`` is
    the 0/1 participation mask over the cohort (padding slots are 0) — or,
    with ``cfg.normalize=False``, the sampler's precomputed aggregation
    weights (nonzero = participant).

    Masking caveat: ``masking="random"`` draws its keep-masks per shard
    (``fold_in(key, axis_index)`` over shard-local rows), so its random
    draws differ from ``make_fed_round``'s full-leaf draws and vary with
    device count — inherent to drawing inside shard_map.  "selective" is
    deterministic in the deltas and matches the full round exactly.
    """
    from functools import partial

    from jax.sharding import PartitionSpec as P

    client_axis = client_axis or fed_layout(mesh)[0]
    n_dev = mesh.shape[client_axis]
    if cohort_size % n_dev != 0:
        raise ValueError(
            f"cohort_size {cohort_size} not divisible by mesh axis "
            f"{client_axis!r} ({n_dev})")

    local_update = _make_local_update(arch, cfg, hints=hints)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(client_axis), P(client_axis), P(client_axis),
                       P()),
             out_specs=(P(), P(), P()),
             check_vma=False)
    def cohort_shard(params, cohort_batches, w_shard, valid_shard, key):
        # Each shard: its slice of the cohort end-to-end — local SGD, mask,
        # codec wire round-trip, weighted partial aggregation — then ONE
        # f32 psum of model size.  The codec runs per client INSIDE the
        # shard_map body, so the bytes each client ships are exactly the
        # wire pytree the strategy meters.
        deltas, losses = jax.vmap(
            lambda b: local_update(params, b))(cohort_batches)
        shard_key = jax.random.fold_in(key, jax.lax.axis_index(client_axis))
        masked = mask_deltas(shard_key, deltas, cfg)
        masked = roundtrip_stacked(cfg.codec, masked)
        agg = jax.lax.psum(_weighted_upload(w_shard, masked), client_axis)
        loss_sum = jax.lax.psum(jnp.sum(losses * valid_shard), client_axis)
        valid_sum = jax.lax.psum(jnp.sum(valid_shard), client_axis)
        return agg, loss_sum, valid_sum

    def fed_round(params, batches, n_samples, cohort_ids, valid, key):
        cohort_batches = jax.tree.map(
            lambda x: jnp.take(x, cohort_ids, axis=0), batches)
        if cfg.normalize:
            w = valid * jnp.take(n_samples, cohort_ids)
            w = w / jnp.maximum(jnp.sum(w), 1e-12)
        else:
            w = valid                 # pre-weighted (sampler coefficients)
        valid01 = (valid > 0).astype(jnp.float32)
        agg, loss_sum, valid_sum = cohort_shard(
            params, cohort_batches, w, valid01, key)
        new_params = jax.tree.map(
            lambda p, a: (p + a.astype(p.dtype)), params, agg)
        metrics = {
            "mean_loss": loss_sum / jnp.maximum(valid_sum, 1.0),
            "num_sampled": valid_sum,
        }
        return new_params, metrics

    return fed_round


# ---------------------------------------------------------------------------
# dry-run entry (called by launch/dryrun.py with --fed)
# ---------------------------------------------------------------------------
def fed_layout(mesh) -> Tuple[str, tuple]:
    """(client_axis, model_fsdp_axes): single-pod -> clients on 'data';
    multi-pod -> clients on 'pod', model over ('data','model')."""
    if "pod" in mesh.axis_names:
        return "pod", ("data",)
    return "data", ()


def lower_fed_round(arch: ArchConfig, shape: InputShape, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    client_axis, fsdp_axes = fed_layout(mesh)
    C = mesh.shape[client_axis]
    fed_cfg = FedPodConfig(num_clients=C)

    # param dtype: fp32 when a client replica fits its silo, else bf16
    # (noted in EXPERIMENTS.md §Dry-run for the affected archs).
    silo_chips = mesh.devices.size // C
    pc = steps_lib.params_specs(arch, "float32")
    import numpy as np
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(pc))
    dtype = "float32" if 4 * n_params / silo_chips < 6e9 else "bfloat16"

    pspecs = steps_lib.params_specs(arch, dtype)
    psh = sh.params_shardings(pspecs, mesh, fsdp=bool(fsdp_axes),
                              fsdp_axes=fsdp_axes or None)

    B, T = shape.global_batch, shape.seq_len
    b_local = max(B // C, 1)
    if arch.modality == "audio_stub" and arch.num_codebooks > 1:
        tok = jax.ShapeDtypeStruct(
            (C, fed_cfg.local_steps, b_local, arch.num_codebooks, T), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct(
            (C, fed_cfg.local_steps, b_local, T), jnp.int32)
    batches = {"tokens": tok, "labels": tok}
    if arch.modality == "vision_stub":
        batches["prefix_embeds"] = jax.ShapeDtypeStruct(
            (C, fed_cfg.local_steps, b_local, arch.num_prefix_embeddings,
             arch.d_model), jnp.bfloat16)

    bsh = jax.tree.map(
        lambda leaf: NamedSharding(mesh, P(client_axis)), batches)
    vec_sh = NamedSharding(mesh, P())
    n_samples = jax.ShapeDtypeStruct((C,), jnp.float32)
    participation = jax.ShapeDtypeStruct((C,), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    from repro.models.hints import Hints
    hints = Hints(dp=(), model="model", model_size=int(mesh.shape["model"]))
    fed_round = make_fed_round(arch, fed_cfg, hints=hints)
    fn = jax.jit(
        fed_round,
        in_shardings=(psh, bsh, vec_sh, vec_sh, vec_sh),
        out_shardings=(psh, sh.replicated(
            {"mean_loss": 0.0, "num_sampled": 0.0}, mesh)),
        donate_argnums=(0,))
    with mesh:
        lowered = fn.lower(pspecs, batches, n_samples, participation, key)
    compiled = lowered.compile()
    return lowered, compiled, {"mesh": mesh, "fed_cfg": fed_cfg,
                               "param_dtype": dtype}
