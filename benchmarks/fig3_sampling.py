"""Paper Fig. 3: static vs dynamic sampling (beta 0.01 / 0.1) on LeNet —
accuracy and transport cost after 10 / 30 rounds of federated training.

Also hosts the cohort-engine execution benchmark (DESIGN.md §3.5): the
full-population vmap runs every registered client each round, so its
per-round wall-clock is flat in c(t); the cohort engine materializes only
the sampled bucket, so wall-clock decays with c(t).  Rows are written to
``BENCH_cohort.json`` at the repo root:

  PYTHONPATH=src python -m benchmarks.fig3_sampling --cohort [--smoke]

``--smoke`` (CI) shrinks the population and round count so regressions
fail fast without tying up a runner.
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DynamicSampling, FederatedServer, StaticSampling,
                        strategy)

from benchmarks.common import make_schedule, run_strategy

BENCH_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                          "BENCH_cohort.json")
# smoke runs (CI) write here so they never clobber the tracked full-run JSON
SMOKE_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                          "BENCH_cohort.smoke.json")


def run():
    rows = []
    # "fig3" is the beta=0.1 preset; the other schedules are field
    # overrides of the same strategy record.
    settings = [
        ("static", strategy.get("dense-baseline")),
        ("dynamic_b0.01", strategy.get(
            "fig3", sampling=make_schedule("dynamic", 0.01))),
        ("dynamic_b0.1", strategy.get("fig3")),
    ]
    for rounds in (10, 30):
        for name, strat in settings:
            r = run_strategy("lenet", strat, rounds)
            rows.append({"figure": "fig3", "sampling": name,
                         "rounds": rounds, **r})
    return rows


# ---------------------------------------------------------------------------
# cohort engine vs full-population vmap
# ---------------------------------------------------------------------------
def _logistic_problem(num_clients, num_batches=2, batch=32, dim=256,
                      classes=10, seed=0):
    """Synthetic softmax regression sized so client_update compute (not the
    model) dominates: the bench isolates execution scaling in the number of
    clients actually run per round."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (num_clients, num_batches, batch, dim),
                          jnp.float32)
    y = jax.random.randint(jax.random.fold_in(key, 1),
                           (num_clients, num_batches, batch), 0, classes)

    def loss_fn(params, data):
        xb, yb = data
        logits = xb @ params["w"] + params["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    params = {
        "w": 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                     (dim, classes)),
        "b": jnp.zeros((classes,)),
    }
    n = np.ones((num_clients,), np.float32)
    return loss_fn, params, (x, y), n


def _steady_rows(server, engine, M):
    recs = server.history
    steady = [r.wall_s for r in recs]
    return {
        "figure": "cohort_engine", "engine": engine, "num_clients": M,
        "rounds": len(recs),
        "cohort_size": recs[-1].cohort_size,
        "num_sampled": recs[-1].num_sampled,
        "steady_wall_ms_per_round": round(1e3 * float(np.mean(steady)), 3),
        "compile_s": round(sum(r.compile_s for r in recs), 2),
    }


def run_cohort(Ms=(64, 256, 1024), rounds=8, smoke=False):
    """Two cases: (a) steady-state at c(t)=0.125 — full baseline vs cohort
    engine per M; (b) a dynamic-decay trace showing per-round wall-clock
    falling with c(t) under the cohort engine."""
    if smoke:
        Ms, rounds = (16,), 2
    rows = []

    # (a) steady state at c = 0.125
    for M in Ms:
        loss_fn, params, batches, n = _logistic_problem(M)
        sched = StaticSampling(initial_rate=0.125, min_clients=2)
        walls = {}
        for engine in ("full", "cohort"):
            strat = strategy.get("dense-baseline", sampling=sched)
            server = FederatedServer.from_strategy(strat, loss_fn, params, M,
                                                   engine=engine)
            server.run(batches, n, rounds)
            row = _steady_rows(server, engine, M)
            walls[engine] = row["steady_wall_ms_per_round"]
            rows.append(row)
        rows[-1]["speedup_vs_full"] = round(
            walls["full"] / max(walls["cohort"], 1e-9), 2)

    # (b) wall-clock decays with c(t) under dynamic sampling
    M = Ms[-1]
    loss_fn, params, batches, n = _logistic_problem(M)
    sched = DynamicSampling(initial_rate=1.0, beta=0.3, min_clients=2)
    strat = strategy.get("fig3", sampling=sched)
    server = FederatedServer.from_strategy(strat, loss_fn, params, M,
                                           engine="cohort")
    server.run(batches, n, rounds if smoke else 2 * rounds)
    for r in server.history:
        rows.append({
            "figure": "cohort_decay", "engine": "cohort", "num_clients": M,
            "round": r.round, "num_sampled": r.num_sampled,
            "cohort_size": r.cohort_size,
            "wall_ms": round(1e3 * r.wall_s, 3),
            "compile_s": round(r.compile_s, 2),
        })

    from benchmarks.common import write_bench
    write_bench(SMOKE_PATH if smoke else BENCH_PATH, "cohort", rows)
    return rows


if __name__ == "__main__":
    from benchmarks.common import fmt_rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--cohort", action="store_true",
                    help="run the cohort-engine bench (writes BENCH_cohort.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny M / 2 rounds for CI")
    args = ap.parse_args()
    if args.cohort or args.smoke:
        print(fmt_rows(run_cohort(smoke=args.smoke)))
    else:
        print(fmt_rows(run()))
