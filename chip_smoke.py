#!/usr/bin/env python3
"""Smoke run of the federated round on a TPU v5e.

    python chip_smoke.py [--seed N]                # one chip
    python chip_smoke.py --four-chips [--seed N]   # four chips

One chip: the paper's main path, the cohort engine behind
``FederatedServer.from_strategy``, trains the paper's GRU language model
with tied embeddings at WikiText-2's vocabulary (33,278 tokens,
d_embed = d_hidden = 128: a 4.26M-element embedding leaf) on seeded Markov
text dealt to 128 registered clients.  It runs twice in this process:

  A  preset ``fig5`` with ``MaskPolicy.selective(0.5, backend="kernel")``
     and the fused Pallas wire codec of ``fig5-fused``;
  B  the same run on the jnp masking path with the ``SparseCodec``
     reference wire of ``fig5``.

Each phase prints its compile and steady round seconds, the loss of every
round, the exact upload wire bytes and the number of compiled Pallas
kernels (``tpu_custom_call``) in its round programs.  The run fails unless
the loss is finite and falls, A's wire bytes equal B's, A's round losses
and trained parameters agree with B's (bounds below), and A's round
program holds at least one compiled kernel.

Four chips: the pod cohort round (``launch/fedtrain.make_cohort_fed_round``,
reached through ``FedPodConfig``) trains qwen2-1.5b at its published widths,
cut in depth, on a 4-chip ``("data",)`` mesh with one cohort client per
chip, against ``make_fed_round`` on one chip with the same participants.
Only that round and its comparison run.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
There is no CPU or interpret-mode path: without a TPU the script exits
non-zero before any training.  Every phase runs in this one process, which
holds the chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import strategy  # noqa: E402
from repro.core.sampling import DynamicSampling  # noqa: E402
from repro.core.server import FederatedServer  # noqa: E402
from repro.core.strategy import MaskPolicy  # noqa: E402
from repro.data import markov_text, partition_text  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import gru_lm_loss, init_gru_lm  # noqa: E402

# ---- one chip: the GRU LM on the cohort engine ----------------------------
VOCAB = 33_278            # WikiText-2's published vocabulary
NUM_CLIENTS = 128         # registered clients
BATCHES, BATCH, SEQ = 2, 4, 32   # per client: 2 local steps of 4 x 32 tokens
# fig5's beta = 0.1 schedule started at a quarter of the fleet: the first
# cohorts hold 29..19 clients (one 32-client bucket), so the vmapped local
# updates, masks and wire round-trips of the whole cohort fit one chip's
# HBM.  At fig5's own initial rate of 1.0 the first cohort is 116 clients.
COHORT_SAMPLING = DynamicSampling(initial_rate=0.25, beta=0.1, min_clients=2)
# Rounds 1-6 run in the 32-client bucket and 7-8 in the 16-client one, so
# both bucket programs (each with its own Pallas kernels) compile and run.
ROUNDS = 8
# Plain SGD on the tied 33,278-way softmax starts at ln(V) with tiny
# activations and moves slowly: at the 0.25 of the GRU LM benchmark
# (benchmarks/fig89_lm.py) five rounds on a v5e moved the mean round loss by
# only 6.6e-4, close to its round-to-round noise, so "the loss falls" would
# hang on that noise.  A step of 1.0 moves it about four times as far.
LEARNING_RATE = 1.0
# A and B differ only in where each leaf's top-k threshold lands (the
# kernel brackets it to ~1%, the jnp bisection to float resolution), so
# entries at the threshold may flip.  The loss barely moves from ln(V), so
# its bound is a share of the fall, not of the loss: a kernel that kept the
# wrong entries or none would stay near 10.41 and pass a 1% bound.  The
# trained parameters are compared too; on a v5e the gap is 3.9028e-3 of
# B's update and 9.896e-5 at most, and each bound is about twice that.
LOSS_GAP_OF_FALL = 0.1    # max over rounds |loss_A - loss_B| / fall of B
PARAM_RTOL = 1e-2         # |theta_A - theta_B| / |theta_B - theta_0|
PARAM_ATOL = 2e-4         # max |theta_A - theta_B|

# ---- four chips: the pod cohort round -------------------------------------
POD_ARCH = "qwen2-1.5b"
# Tried in order, the first that fits is run.  Compiled for a described v5e,
# the one-chip comparison needs 10.4 GiB at depth 4 and 15.5 GiB at depth 5.
POD_DEPTHS = (4, 2, 1)
POD_CLIENTS, POD_STEPS, POD_BATCH, POD_SEQ = 4, 2, 1, 256
POD_VALID = (1.0, 1.0, 0.0, 1.0)   # 3 participants, one padding slot
# Entries further apart than FLIP_TOL are selective-mask threshold flips: an
# entry within rounding of its leaf's top-k threshold is kept by one program
# and dropped by the other, because a batch-4 and a batch-1 local update
# round differently.  On the CPU the 8-device cohort test finds 16 such of
# 623,616 entries (a 2.6e-5 share), with the bf16 upload and an f32 one
# alike; the share bound is about three times that.  A flip moves an entry
# by one client's weighted delta at the threshold, so its size is bounded
# too: twice the 1.3466e-3 largest gap this round showed on four v5e chips.
FLIP_TOL = 2e-3
MAX_FLIP_SHARE = 7.5e-5
POD_DPARAMS_MAX = 2.7e-3
# bf16 activations (the arch's compute dtype) summed in another order by
# the batch-4 program than by the batch-1 shards: 1.52e-5 relative on four
# v5e chips, and the bound is about three times that.
POD_LOSS_RTOL = 5e-5


def require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {d.platform!r} "
            f"({d.device_kind}), not a TPU; this smoke has no CPU path")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"check ok: {what}", flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def client_text(seed: int):
    """(inputs, targets) of shape (M, BATCHES, BATCH, SEQ) and per-client
    sample counts, dealt IID from one seeded Markov stream."""
    per_client = BATCHES * BATCH
    d = markov_text(num_train=NUM_CLIENTS * per_client * SEQ + 1,
                    num_test=16, vocab_size=VOCAB, seed=seed)
    x, y, n = partition_text(d.train_tokens, NUM_CLIENTS, BATCH, SEQ,
                             seed=seed)
    assert x.shape == (NUM_CLIENTS, BATCHES, BATCH, SEQ), x.shape
    return (jnp.asarray(x), jnp.asarray(y)), n


def federated_phase(name: str, strat, batches, n_samples, seed: int):
    """Train ``ROUNDS`` rounds through the server; report the phase's
    numbers and return them with the trained parameters."""
    params = init_gru_lm(jax.random.PRNGKey(seed), VOCAB)
    server = FederatedServer.from_strategy(
        strat, gru_lm_loss, params, NUM_CLIENTS, seed=seed,
        scan_rounds=False)
    print(f"phase {name}: masking={strat.masking.mode}/"
          f"{strat.masking.backend} gamma={strat.masking.gamma} "
          f"codec={strat.codec.name}", flush=True)
    server.run(batches, n_samples, ROUNDS)
    hist = server.history
    for r in hist:
        print(f"phase {name} round {r.round}: loss={r.mean_loss!r} "
              f"sampled={r.num_sampled} cohort={r.cohort_size} "
              f"wall_s={r.wall_s!r} compile_s={r.compile_s!r}", flush=True)
    steady = [r.wall_s for r in hist if r.compile_s == 0.0]
    summary = server.summary()
    out = {
        "phase": name,
        "losses": [r.mean_loss for r in hist],
        "compile_s": summary["compile_s"],
        "steady_round_s_median": (statistics.median(steady)
                                  if steady else None),
        "client_upload_bytes": summary["client_upload_bytes"],
        "transport_bytes": summary["transport_bytes"],
        "tpu_custom_call": sum(t.count("tpu_custom_call")
                               for t in server.compiled_hlo()),
    }
    print(f"phase {name}: " + json.dumps(out), flush=True)
    return out, server.params


@jax.jit
def param_gap(pa, pb, p0):
    """(|pa - pb| / |pb - p0| over the whole tree, max |pa - pb|)."""
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree.leaves(t))
    d = jax.tree.map(jnp.subtract, pa, pb)
    dmax = jnp.max(jnp.stack([jnp.max(jnp.abs(x))
                              for x in jax.tree.leaves(d)]))
    return jnp.sqrt(sq(d) / sq(jax.tree.map(jnp.subtract, pb, p0))), dmax


def one_chip(seed: int) -> None:
    batches, n_samples = client_text(seed)
    gamma = strategy.get("fig5").masking.gamma
    a, params_a = federated_phase("A", strategy.get(
        "fig5", masking=MaskPolicy.selective(gamma, backend="kernel"),
        codec=strategy.get("fig5-fused").codec, sampling=COHORT_SAMPLING,
        learning_rate=LEARNING_RATE),
        batches, n_samples, seed)
    b, params_b = federated_phase("B", strategy.get(
        "fig5", masking=MaskPolicy.selective(gamma, backend="jnp"),
        codec=strategy.get("fig5").codec, sampling=COHORT_SAMPLING,
        learning_rate=LEARNING_RATE),
        batches, n_samples, seed)
    for p in (a, b):
        check(all(np.isfinite(p["losses"])),
              f"phase {p['phase']} losses are finite")
        check(p["losses"][-1] < p["losses"][0],
              f"phase {p['phase']} loss falls: {p['losses'][0]!r} -> "
              f"{p['losses'][-1]!r}")
    check(a["tpu_custom_call"] >= 1,
          f"phase A round compiled {a['tpu_custom_call']} Pallas kernels")
    check(a["client_upload_bytes"] == b["client_upload_bytes"]
          and a["transport_bytes"] == b["transport_bytes"],
          f"wire bytes A == B: {a['client_upload_bytes']} per upload, "
          f"{a['transport_bytes']} in all")
    gap = max(abs(x - y) for x, y in zip(a["losses"], b["losses"]))
    fall = b["losses"][0] - b["losses"][-1]
    check(gap <= LOSS_GAP_OF_FALL * fall,
          f"round losses A vs B differ by at most {gap!r}, within "
          f"{LOSS_GAP_OF_FALL} of B's fall {fall!r}")
    rel, dmax = (float(v) for v in param_gap(
        params_a, params_b, init_gru_lm(jax.random.PRNGKey(seed), VOCAB)))
    check(rel <= PARAM_RTOL and dmax <= PARAM_ATOL,
          f"trained params A vs B: |A - B| / |B - init| = {rel!r} (bound "
          f"{PARAM_RTOL}), max |A - B| = {dmax!r} (bound {PARAM_ATOL})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def _memory_budget(device) -> float:
    """Bytes one program may take on ``device``: 90% of what the runtime
    lets it allocate."""
    return 0.9 * device.memory_stats()["bytes_limit"]


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)


def four_chips(seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_arch
    from repro.launch.fedtrain import (FedPodConfig, make_cohort_fed_round,
                                       make_fed_round)
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tr

    devs = jax.devices()
    check(len(devs) == 4, f"four chips present ({len(devs)} found)")
    C = POD_CLIENTS
    one = SingleDeviceSharding(devs[0])
    mesh = make_mesh((4,), ("data",))
    rep = NamedSharding(mesh, P())
    fed_cfg = FedPodConfig(num_clients=C, local_steps=POD_STEPS,
                           learning_rate=0.05, gamma=0.3)
    key = jax.random.PRNGKey(seed)
    tok_shape = (C, POD_STEPS, POD_BATCH, POD_SEQ)
    valid = jnp.asarray(POD_VALID, jnp.float32)
    ids = jnp.arange(C, dtype=jnp.int32)
    budget = _memory_budget(devs[0])

    # The one-chip comparison holds all C clients' local updates at once,
    # so it bounds the depth: take the deepest that fits, by the compiler's
    # own memory analysis.
    base = get_arch(POD_ARCH)
    for depth in POD_DEPTHS:
        arch = dataclasses.replace(base, num_layers=depth)
        pspec = jax.eval_shape(
            lambda k: tr.init_params(k, arch, "bfloat16"), key)
        batch_spec = {"tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
                      "labels": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
        vec = jax.ShapeDtypeStruct((C,), jnp.float32)
        t0 = time.perf_counter()
        ref = jax.jit(make_fed_round(arch, fed_cfg)).lower(
            *_shapes((pspec, batch_spec, vec, vec, key), one)).compile()
        ref_compile_s = time.perf_counter() - t0
        need = _device_bytes(ref)
        print(f"pod depth {depth}: one-chip round needs {need} bytes of a "
              f"{int(budget)}-byte budget (compile {ref_compile_s!r} s)",
              flush=True)
        if need <= budget:
            break
    else:
        raise SystemExit("chip_smoke: FAILED: no depth of "
                         f"{POD_ARCH} fits one chip")
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(pspec))
    print(f"pod model: {POD_ARCH} published widths, {depth} of "
          f"{base.num_layers} layers, {n_params} params (bf16), "
          f"{C} clients x {POD_STEPS} steps x {POD_BATCH}x{POD_SEQ} tokens",
          flush=True)

    t0 = time.perf_counter()
    cohort = jax.jit(make_cohort_fed_round(
        arch, fed_cfg, cohort_size=C, mesh=mesh, client_axis="data")).lower(
        *_shapes((pspec, batch_spec, vec), rep),
        jax.ShapeDtypeStruct((C,), jnp.int32, sharding=rep),
        *_shapes((vec, key), rep)).compile()
    cohort_compile_s = time.perf_counter() - t0
    print(f"pod cohort round: {_device_bytes(cohort)} bytes per chip "
          f"(compile {cohort_compile_s!r} s)", flush=True)

    params = jax.jit(lambda k: tr.init_params(k, arch, "bfloat16"),
                     out_shardings=one)(key)
    toks = jax.random.randint(jax.random.fold_in(key, 1), tok_shape, 0,
                              arch.vocab_size)
    batches = {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}
    n_samples = jnp.ones((C,), jnp.float32)
    part = jnp.zeros((C,), jnp.float32).at[ids].set(valid)
    rkey = jax.random.fold_in(key, 2)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))          # first call
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        return out, time.perf_counter() - t0

    ref_args = jax.device_put((params, batches, n_samples, part, rkey), one)
    (p_f, m_f), ref_s = timed(ref, *ref_args)
    coh_args = jax.device_put(
        (params, batches, n_samples, ids, valid, rkey), rep)
    (p_c, m_c), coh_s = timed(cohort, *coh_args)
    p_c = jax.device_put(p_c, one)

    @jax.jit
    def compare(a, c):
        d = [jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))]
        return (jnp.max(jnp.stack([jnp.max(x) for x in d])),
                sum(jnp.sum(x > FLIP_TOL) for x in d))

    dmax, n_flip = (float(v) for v in compare(p_f, p_c))
    res = {
        "depth": depth, "num_params": n_params,
        "num_sampled_one_chip": float(m_f["num_sampled"]),
        "num_sampled_cohort": float(m_c["num_sampled"]),
        "loss_one_chip": float(m_f["mean_loss"]),
        "loss_cohort": float(m_c["mean_loss"]),
        "dparams_max": dmax, "entries_over_flip_tol": int(n_flip),
        "one_chip_compile_s": ref_compile_s, "one_chip_round_s": ref_s,
        "cohort_compile_s": cohort_compile_s, "cohort_round_s": coh_s,
    }
    print("pod: " + json.dumps(res), flush=True)
    check(res["num_sampled_one_chip"] == res["num_sampled_cohort"]
          == sum(POD_VALID), "same participants in both rounds")
    rel = abs(res["loss_one_chip"] - res["loss_cohort"]) / abs(
        res["loss_one_chip"])
    check(np.isfinite(res["loss_cohort"]) and rel <= POD_LOSS_RTOL,
          f"round loss agrees within {POD_LOSS_RTOL}: relative {rel!r}")
    check(n_flip <= MAX_FLIP_SHARE * n_params,
          f"{int(n_flip)} of {n_params} parameters differ by more than "
          f"{FLIP_TOL} (at most a {MAX_FLIP_SHARE} share of "
          f"threshold flips)")
    check(dmax <= POD_DPARAMS_MAX,
          f"largest parameter gap {dmax!r} within {POD_DPARAMS_MAX}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod cohort round on a 4-chip mesh "
                         "and its one-chip comparison")
    args = ap.parse_args()
    device = require_tpu()
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
