"""Engine for the cells that run ``FederatedServer``: the paper's
simulation path (cohort engine, dense client-state store).

Set-up builds one server from the cell's strategy and drives it from the
seed through the first ``reference.ROUNDS_CHECKED`` rounds with the same
call the window makes, ``server.run(batches, n_samples, 1)``; the first
of them compiles (or loads from the cache) the cell's one round program.
The window then runs whole rounds, closed loop, until ``seconds`` have
passed.  Once it has closed and the peak memory has been read, the server
is freed and the plain reference re-runs the first rounds for the check.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import counts, reference


def _strategy(spec: dict):
    from repro.core import strategy
    from repro.core.sampling import StaticSampling
    from repro.core.strategy import MaskPolicy

    s = spec["sampling"]
    over = {"sampling": StaticSampling(initial_rate=s["rate"],
                                       min_clients=s["min_clients"]),
            "learning_rate": spec["learning_rate"],
            "local_epochs": spec["local_epochs"]}
    m = spec["masking"]
    if m["mode"] == "none":
        over["masking"] = MaskPolicy.none()
    else:
        over["masking"] = MaskPolicy.selective(m["gamma"],
                                               backend=m["backend"])
    if "codec_of" in spec:
        over["codec"] = strategy.get(spec["codec_of"]).codec
    return strategy.get(spec["preset"], **over)


def _block(x):
    jax.block_until_ready(x)
    return x


def run(job) -> dict:
    """Set up, measure and check one run of the cell ``job.cell``."""
    from repro.core.codecs import FusedSparseCodec
    from repro.core.server import FederatedServer

    cell, cfg, model = job.cell, job.config, job.model
    traffic, spec = cell["traffic"], cell["strategy"]
    seed = job.seed % 2 ** 32
    k_params, k_data = jax.random.split(jax.random.PRNGKey(seed))
    batches, n_samples = _block(job.traffic.generate(k_data, cfg, traffic))
    params0 = _block(jax.jit(lambda k: model.init_params(k, cfg))(k_params))
    strat = _strategy(spec)
    num_clients = traffic["num_clients"]
    server = FederatedServer.from_strategy(
        strat, model.loss_fn(cfg), params0, num_clients, seed=seed,
        scan_rounds=False)

    prog = {"p0": params0, "losses": []}
    for r in range(1, reference.ROUNDS_CHECKED + 1):
        server.run(batches, n_samples, 1)
        prog["losses"].append(server.history[-1].mean_loss)
        if r == 1:
            prog["p1"] = server.params
        prog["p3"] = server.params
    _block(server.params)
    setup_s = time.perf_counter() - job.clock0

    tracer = job.tracer() if job.trace else None
    window = []
    start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.round"):
            server.run(batches, n_samples, 1)
        window.append(server.history[-1])
        if time.perf_counter() - start >= job.seconds:
            break
    elapsed = time.perf_counter() - start
    trace = tracer.stop() if tracer else None
    memory_peak = job.memory_peak()

    updates = sum(r.num_sampled for r in window)
    bucket = window[0].cohort_size
    upload_bytes = int(server.client_upload_bytes)
    leaf_sizes = [x.size for x in jax.tree.leaves(params0)]
    del server

    ref = reference.settings(cell)
    ref_p0 = jax.jit(lambda k: model.ref_init(k, cfg))(k_params)
    ref_losses, ref_trail = reference.run_rounds(
        model.ref_loss, ref_p0, batches, n_samples,
        reference.cell_cohorts(cell, seed), **ref)
    checks = reference.readings(prog, ref_losses, ref_trail, ref_p0)

    leaf = jax.tree.leaves(batches)[0]
    examples = spec["local_epochs"] * leaf.shape[1] * leaf.shape[2]
    kernel_bytes = None
    if strat.masking.mode == "selective" and strat.masking.backend == "kernel":
        fused = isinstance(strat.codec, FusedSparseCodec)
        kernel_bytes = bucket * counts.mask_kernel_bytes(
            counts.maskable_elements(leaf_sizes, ref["min_leaf_size"]),
            ref["gamma"], codec_fused=fused,
            quantize=fused and strat.codec.quantized)
    bad = [r for r in window if not np.isfinite(r.mean_loss)
           or r.quarantined or r.compile_s > 0]
    return {
        "end_to_end": {
            "client_updates_per_s": updates / elapsed,
            "upload_bytes": upload_bytes,
            "setup_s": setup_s,
        },
        "attempted": len(window),
        "failed": len(bad),
        "memory_peak_bytes": memory_peak,
        "checks": checks,
        "trace": trace,
        "context": {
            "round_updates": [r.num_sampled for r in window],
            "flops_per_update": examples * model.example_flops(cfg, traffic),
            "mask_kernel_bytes_per_round": kernel_bytes,
            "bucket": bucket,
        },
    }
