"""Cohort execution engine (DESIGN.md §3.5) equivalence tests.

The cohort engine must be a pure execution optimization: running only the
sampled m_t clients (padded to a static bucket) has to produce the SAME
round results as the full-population vmap oracle — params, residuals,
mean_loss, num_sampled — across bucket boundaries and with error feedback
on/off.  Params/residuals are compared bit-exactly: the cohort keeps ids
sorted ascending and the oracle's extra terms are exact zeros, so the
weighted reductions agree to the ulp.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st

from repro.core import (ClientConfig, DynamicSampling, FederatedConfig,
                        FederatedServer, MaskingConfig, StaticSampling)
from repro.core.codecs import SparseCodec
from repro.core.federated import (cohort_select, make_cohort_round,
                                  make_cohort_scan, make_federated_round,
                                  make_store_round)
from repro.core.sampling import participation_mask


@functools.lru_cache()
def _problem(num_clients, dim=8, classes=3, num_batches=2, batch=4, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (num_clients, num_batches, batch, dim))
    y = jax.random.randint(jax.random.fold_in(key, 1),
                           (num_clients, num_batches, batch), 0, classes)

    def loss_fn(params, data):
        xb, yb = data
        logp = jax.nn.log_softmax(xb @ params["w"] + params["b"])
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    params = {"w": 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                           (dim, classes)),
              "b": jnp.zeros((classes,))}
    n = jnp.ones((num_clients,), jnp.float32)
    return loss_fn, params, (x, y), n


def _zero_residuals(params, num_clients):
    return jax.tree.map(
        lambda p: jnp.zeros((num_clients,) + p.shape, p.dtype), params)


def _assert_trees_equal(a, b, exact=True):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        else:
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-6, atol=1e-7)


def test_cohort_select_matches_participation_mask():
    """Same key => cohort valid-members are exactly the oracle's mask."""
    sched = DynamicSampling(initial_rate=0.9, beta=0.2, min_clients=2)
    for t in range(1, 6):
        key = jax.random.PRNGKey(t)
        mask = participation_mask(key, sched, jnp.float32(t), 16)
        m = sched.num_clients_host(t, 16)
        bucket = sched.bucket_for(m, 16)
        ids, valid = cohort_select(key, sched, jnp.float32(t), 16, bucket)
        got = np.zeros(16, np.float32)
        got[np.asarray(ids)] = np.asarray(valid)
        np.testing.assert_array_equal(got, np.asarray(mask))
        assert int(valid.sum()) == m


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=5, max_value=16),
       st.floats(min_value=0.0, max_value=0.4),
       st.booleans())
def test_cohort_round_matches_oracle(num_clients, beta, error_feedback):
    """Property: every round's (params, residuals, mean_loss, num_sampled)
    from the cohort engine matches the full-vmap oracle, across the bucket
    boundaries the decaying schedule walks through."""
    loss_fn, params, batches, n = _problem(num_clients)
    sched = DynamicSampling(initial_rate=1.0, beta=beta, min_clients=2)
    cfg = FederatedConfig(
        num_clients=num_clients,
        client=ClientConfig(local_epochs=1, learning_rate=0.1,
                            masking=MaskingConfig(mode="selective",
                                                  gamma=0.4)),
        error_feedback=error_feedback)
    oracle = jax.jit(make_federated_round(loss_fn, sched, cfg))

    p_o = p_c = params
    r_o = r_c = _zero_residuals(params, num_clients)
    key = jax.random.PRNGKey(int(num_clients * 7 + beta * 100))
    buckets_seen = set()
    for t in range(1, 7):
        key, sub = jax.random.split(key)
        t_arg = jnp.asarray(t, jnp.float32)
        m = sched.num_clients_host(t, num_clients)
        bucket = sched.bucket_for(m, num_clients)
        buckets_seen.add(bucket)
        p_o, r_o, met_o = oracle(p_o, r_o, batches, n, t_arg, sub)
        if bucket >= num_clients:
            fn = oracle
        else:
            fn = jax.jit(make_cohort_round(loss_fn, sched, cfg, bucket))
        p_c, r_c, met_c = fn(p_c, r_c, batches, n, t_arg, sub)

        assert int(met_o["num_sampled"]) == int(met_c["num_sampled"]) == m
        np.testing.assert_allclose(float(met_o["mean_loss"]),
                                   float(met_c["mean_loss"]),
                                   rtol=1e-6, atol=1e-6)
        _assert_trees_equal(p_o, p_c)
        _assert_trees_equal(r_o, r_c)
    if beta > 0.2:      # the schedule actually crossed a bucket boundary
        assert len(buckets_seen) > 1, buckets_seen


def test_cohort_scan_matches_round_loop():
    """The lax.scan fast path is the same program as the per-round loop."""
    M = 8
    loss_fn, params, batches, n = _problem(M)
    sched = StaticSampling(initial_rate=0.5, min_clients=2)
    cfg = FederatedConfig(
        num_clients=M,
        client=ClientConfig(local_epochs=1, learning_rate=0.1,
                            masking=MaskingConfig(mode="selective",
                                                  gamma=0.4)),
        error_feedback=True)
    bucket = sched.bucket_for(sched.num_clients_host(1, M), M)
    round_fn = jax.jit(make_cohort_round(loss_fn, sched, cfg, bucket))
    scan_fn = jax.jit(make_cohort_scan(loss_fn, sched, cfg, bucket))

    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    ts = jnp.arange(1, 5, dtype=jnp.float32)
    p, r = params, _zero_residuals(params, M)
    losses = []
    for t, k in zip(ts, keys):
        p, r, met = round_fn(p, r, batches, n, t, k)
        losses.append(float(met["mean_loss"]))
    p_s, r_s, met_s = scan_fn(params, _zero_residuals(params, M), batches,
                              n, ts, keys)
    _assert_trees_equal(p, p_s)
    _assert_trees_equal(r, r_s)
    np.testing.assert_allclose(np.asarray(met_s["mean_loss"]),
                               np.asarray(losses), rtol=1e-6)


@pytest.mark.parametrize("mode,codec", [
    ("none", None),
    ("selective", None),
    ("selective", SparseCodec(gamma=0.4, min_leaf_size=256)),
], ids=["dense", "selective", "selective-coo"])
def test_padded_bucket_forms_bit_identical(mode, codec):
    """A uniform round whose bucket is padded (m_t < bucket < M), error
    feedback on: the cohort round, the store round driven by hand (select
    → gather → body → scatter) and the oracle give bit-identical params,
    residuals and metrics, round after round."""
    M, rounds = 16, 3
    loss_fn, params, batches, n = _problem(M, dim=128)   # w: 384 > 256
    sched = StaticSampling(initial_rate=0.3, min_clients=2)
    m = sched.num_clients_host(1, M)
    bucket = sched.bucket_for(m, M)
    assert m < bucket < M, (m, bucket)
    cfg = FederatedConfig(
        num_clients=M,
        client=ClientConfig(local_epochs=1, learning_rate=0.1,
                            masking=MaskingConfig(mode=mode, gamma=0.4)),
        error_feedback=True)
    oracle = jax.jit(make_federated_round(loss_fn, sched, cfg, codec=codec))
    cohort = jax.jit(make_cohort_round(loss_fn, sched, cfg, bucket,
                                       codec=codec))
    store = make_store_round(loss_fn, sched, cfg, bucket, codec=codec)
    select, body = jax.jit(store.select), jax.jit(store.body)
    assert not store.with_drop and not store.adaptive

    def by_hand(p, r, t, key):
        sample_key, mask_key = jax.random.split(key)
        part, weights, ids = select(None, n, t, sample_key)
        rows = jax.tree.map(lambda x: jnp.take(x, ids, axis=0), (r, batches))
        p, new_rows, _, commit, norm_upd, met = body(
            p, rows[0], None, rows[1], ids, part, weights, None, mask_key,
            None)
        assert norm_upd is None

        def scatter(old, new):
            keep = commit.reshape((-1,) + (1,) * (new.ndim - 1)) > 0
            return old.at[ids].set(jnp.where(keep, new, old[ids]))

        return p, jax.tree.map(scatter, r, new_rows), met

    res0 = _zero_residuals(params, M)
    states = {"oracle": (params, res0), "cohort": (params, res0),
              "store": (params, res0)}
    key = jax.random.PRNGKey(11)
    for t in range(1, rounds + 1):
        key, sub = jax.random.split(key)
        t_arg = jnp.asarray(t, jnp.float32)
        out = {"oracle": oracle(*states["oracle"], batches, n, t_arg, sub),
               "cohort": cohort(*states["cohort"], batches, n, t_arg, sub),
               "store": by_hand(*states["store"], t_arg, sub)}
        for form, (p, r, met) in out.items():
            states[form] = (p, r)
            assert int(met["num_sampled"]) == m
            _assert_trees_equal(p, out["oracle"][0])
            _assert_trees_equal(r, out["oracle"][1])
            for k in ("mean_loss", "num_sampled", "quarantined"):
                np.testing.assert_array_equal(
                    np.asarray(met[k]), np.asarray(out["oracle"][2][k]),
                    err_msg=f"{form} {k} round {t}")
    if mode == "selective":     # masking binds: residuals carry mass
        assert float(jnp.abs(states["oracle"][1]["w"]).max()) > 0.0


def test_server_engines_match():
    """FederatedServer end-to-end: engine="cohort" (with scan segments)
    reproduces engine="full" histories and final params; cohort-aware
    records expose the decaying executed cohort and compile/steady split."""
    M = 16
    loss_fn, params, batches, n = _problem(M)
    sched = DynamicSampling(initial_rate=1.0, beta=0.25, min_clients=2)

    servers = {}
    for engine in ("full", "cohort"):
        cfg = FederatedConfig(
            num_clients=M,
            client=ClientConfig(local_epochs=1, learning_rate=0.1,
                                masking=MaskingConfig(mode="selective",
                                                      gamma=0.4)),
            error_feedback=True)
        s = FederatedServer(loss_fn, sched, cfg, params, seed=5,
                            engine=engine)
        s.run(batches, np.asarray(n), rounds=8)
        servers[engine] = s

    full, cohort = servers["full"], servers["cohort"]
    _assert_trees_equal(full.params, cohort.params)
    assert [r.num_sampled for r in full.history] == \
        [r.num_sampled for r in cohort.history]
    np.testing.assert_allclose(
        [r.mean_loss for r in full.history],
        [r.mean_loss for r in cohort.history], rtol=1e-5, atol=1e-6)

    # cohort-aware records: executed cohort decays with c(t) and is always
    # a bucket >= m_t; the full engine stays flat at M
    coh = [r.cohort_size for r in cohort.history]
    assert all(r.cohort_size == M for r in full.history)
    assert all(b >= r.num_sampled for b, r in zip(coh, cohort.history))
    assert coh[-1] < M and all(a >= b for a, b in zip(coh, coh[1:]))
    assert all(b in sched.bucket_ladder(M) for b in coh)

    # compile_s is metered on bucket-change rounds only; wall_s elsewhere
    changes = [i for i in range(len(coh)) if i == 0 or coh[i] != coh[i - 1]]
    for i, r in enumerate(cohort.history):
        if i in changes:
            assert r.compile_s > 0.0
        else:
            assert r.compile_s == 0.0


def test_server_full_rate_uses_oracle_program():
    """At rate 1.0 the only bucket is M, so the cohort engine dispatches the
    oracle program — one compile for the whole run."""
    M = 8
    loss_fn, params, batches, n = _problem(M)
    cfg = FederatedConfig(
        num_clients=M,
        client=ClientConfig(local_epochs=1, learning_rate=0.1,
                            masking=MaskingConfig(mode="none")))
    s = FederatedServer(loss_fn, StaticSampling(initial_rate=1.0), cfg,
                        params, engine="cohort")
    s.run(batches, np.asarray(n), rounds=4)
    assert len(s._compiled) == 1
    assert all(r.cohort_size == M for r in s.history)
    assert sum(1 for r in s.history if r.compile_s > 0) == 1


def test_sharded_cohort_fed_round_matches_full():
    """launch/fedtrain.make_cohort_fed_round on a 1-device mesh reproduces
    the full pod round when the cohort covers the participants."""
    from repro.configs import get_arch
    from repro.launch.fedtrain import (FedPodConfig, make_cohort_fed_round,
                                       make_fed_round)
    from repro.models import transformer as tr

    cfg = get_arch("qwen2-1.5b").reduced()
    C, S, b, T = 4, 2, 2, 32
    fed_cfg = FedPodConfig(num_clients=C, local_steps=S, learning_rate=0.5,
                           gamma=0.3)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (C, S, b, T), 0, cfg.vocab_size)
    batches = {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}
    n_samples = jnp.ones((C,), jnp.float32)
    part = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    full = jax.jit(make_fed_round(cfg, fed_cfg))
    p_f, m_f = full(params, batches, n_samples, part, key)
    cohort = jax.jit(make_cohort_fed_round(cfg, fed_cfg, cohort_size=4,
                                           mesh=mesh))
    ids = jnp.arange(4, dtype=jnp.int32)
    p_c, m_c = cohort(params, batches, n_samples, ids, part, key)

    assert int(m_f["num_sampled"]) == int(m_c["num_sampled"]) == 3
    np.testing.assert_allclose(float(m_f["mean_loss"]),
                               float(m_c["mean_loss"]), rtol=1e-6)
    for a, b2 in zip(jax.tree_util.tree_leaves(p_f),
                     jax.tree_util.tree_leaves(p_c)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b2, np.float32),
                                   rtol=1e-3, atol=1e-4)


COHORT_SHARD_CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import jax.numpy as jnp
import numpy as np
from repro.configs import get_arch
from repro.launch.fedtrain import (FedPodConfig, make_cohort_fed_round,
                                   make_fed_round)
from repro.models import transformer as tr

cfg = get_arch("qwen2-1.5b").reduced()
C, S, b, T = 16, 1, 1, 16          # 16 registered clients, cohort of 8
fed_cfg = FedPodConfig(num_clients=C, local_steps=S, learning_rate=0.5,
                       gamma=0.3)
mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
params = tr.init_params(jax.random.PRNGKey(0), cfg)
key = jax.random.PRNGKey(1)
toks = jax.random.randint(key, (C, S, b, T), 0, cfg.vocab_size)
batches = {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}
n_samples = jnp.ones((C,), jnp.float32)

# participants: 5 of the 16 clients; cohort buffer of 8 (1 client/device)
ids = jnp.asarray([1, 3, 4, 7, 9, 12, 13, 15], jnp.int32)
valid = jnp.asarray([1, 1, 0, 1, 0, 1, 0, 1], jnp.float32)
part = jnp.zeros((C,)).at[ids].set(valid)

full = jax.jit(make_fed_round(cfg, fed_cfg))
p_f, m_f = full(params, batches, n_samples, part, key)
cohort = jax.jit(make_cohort_fed_round(cfg, fed_cfg, cohort_size=8,
                                       mesh=mesh, client_axis="data"))
p_c, m_c = cohort(params, batches, n_samples, ids, valid, key)

diffs = [np.abs(np.asarray(a, np.float32) - np.asarray(c, np.float32))
         for a, c in zip(jax.tree_util.tree_leaves(p_f),
                         jax.tree_util.tree_leaves(p_c))]
print(json.dumps({"num_sampled_full": float(m_f["num_sampled"]),
                  "num_sampled_cohort": float(m_c["num_sampled"]),
                  "loss_full": float(m_f["mean_loss"]),
                  "loss_cohort": float(m_c["mean_loss"]),
                  "dparams_max": max(float(d.max()) for d in diffs),
                  "over_2e-3": int(sum((d > 2e-3).sum() for d in diffs)),
                  "num_params": int(sum(d.size for d in diffs))}))
"""


def test_sharded_cohort_round_subprocess_8dev():
    """shard_map cohort round on 8 forced host devices (1 cohort client per
    device) matches the full-population pod round: same participants, same
    loss, and params within 2e-3 except for selective-mask threshold
    flips."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", COHORT_SHARD_CHECK], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["num_sampled_full"] == rec["num_sampled_cohort"] == 5.0
    np.testing.assert_allclose(rec["loss_full"], rec["loss_cohort"],
                               rtol=1e-5)
    # Params beyond 2e-3 are threshold flips, not upload precision (an f32
    # upload gives the same 0.0125 max): a batch-16 and a batch-1 local
    # update round differently, so an entry at its leaf's top-k threshold
    # is kept by one round and dropped by the other.  16 of 623,616 entries
    # flip, by at most 0.0125; both bounds are about 2-3x that.
    assert rec["over_2e-3"] <= 7.5e-5 * rec["num_params"], rec
    assert rec["dparams_max"] <= 0.025, rec


def test_cohort_round_rejects_bad_bucket():
    loss_fn, params, batches, n = _problem(8)
    cfg = FederatedConfig(num_clients=8, client=ClientConfig())
    with pytest.raises(ValueError):
        make_cohort_round(loss_fn, StaticSampling(), cfg, 0)
    with pytest.raises(ValueError):
        make_cohort_round(loss_fn, StaticSampling(), cfg, 9)
    with pytest.raises(ValueError):
        make_cohort_scan(loss_fn, StaticSampling(), cfg, 9)
