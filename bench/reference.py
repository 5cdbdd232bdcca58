"""Plain reference of the federated round, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  It restates the round that
the cells run: the server's seeded choice of each round's cohort, plain
local SGD on every participant (the configuration's reference loss),
exact per-leaf top-k masking of each delta (the exact k-th magnitude,
where the program brackets it), a lossless wire (the COO and fused codecs carry a
masked delta exactly), and the sample-weighted FedAvg mean.

Each number compared is a gap between what the program's timed path
produced in its first rounds and what the reference gives from the same
seed:

* ``loss_gap``: the largest relative gap of a round's mean client loss;
* ``loss1_gap``: the relative gap of round 1's mean client loss;
* ``update1_gap``: round 1's update of the global parameters, as the
  aggregator hands it to the model, compared by the worst leaf;
* ``update1_diff``: the same update, compared entry by entry by the worst
  leaf, so that values put at the wrong indices show where norms agree;
* ``change3_gap``: the change of the global parameters over three rounds,
  compared by the worst leaf;
* ``change3_median_gap``: the same change, compared by the median leaf.

A cell compares the numbers that its ``check.limits`` names; the result
line's ``check_detail`` carries the others, not compared.

A leaf is compared by the gap between the two norms (the program's and the
reference's), or for ``update1_diff`` by the norm of their difference,
over the reference's norm of that leaf or of the median leaf, whichever is
larger.  Leaves whose reference update in round 1 is
under a thousandth of the median leaf's are left out.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

ROUNDS_CHECKED = 3
FLOOR_SHARE = 1e-3
MIN_LEAF_SIZE = 256     # leaves under this pass the program's masking whole


# ---- the server's seeded cohorts --------------------------------------------
def cohort_size(rate: float, num_clients: int, min_clients: int) -> int:
    """Participants of a round at a static rate: ``round(rate * M)``,
    at least ``min_clients`` and at most ``M``."""
    m = int(np.round(np.float32(rate) * np.float32(num_clients)))
    return max(min(m, num_clients), min(min_clients, num_clients))


def settings(cell: dict) -> dict:
    """The keyword settings of :func:`run_rounds` that a cell states: step
    size, local epochs, masking, and the block of clients computed at
    once."""
    spec, masking = cell["strategy"], cell["strategy"]["masking"]
    return dict(lr=spec["learning_rate"], epochs=spec["local_epochs"],
                gamma=masking["gamma"] if masking["mode"] != "none" else 1.0,
                min_leaf_size=MIN_LEAF_SIZE,
                block=cell["check"]["block"])


def cell_cohorts(cell: dict, seed: int) -> list:
    """The participants of the checked rounds of a cell's run."""
    sampling, n = cell["strategy"]["sampling"], cell["traffic"]["num_clients"]
    m = cohort_size(sampling["rate"], n, sampling["min_clients"])
    return cohorts(seed, n, m, ROUNDS_CHECKED)


def cohorts(seed: int, num_clients: int, m: int, rounds: int) -> list:
    """The sorted ids of each round's participants.  The server draws a
    round key by splitting its seeded key; the round splits that into a
    sampling and a masking key, and the participants are the ``m`` clients
    with the lowest uniform scores under the sampling key."""
    key = jax.random.PRNGKey(seed)
    out = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        sample_key, _ = jax.random.split(sub)
        scores = jax.random.uniform(sample_key, (num_clients,))
        out.append(np.sort(np.asarray(jnp.argsort(scores))[:m]))
    return out


# ---- one round ----------------------------------------------------------------
def _kth_largest(mag, k: int):
    """The ``k``-th largest of the non-negative ``mag``, exactly: a binary
    search over the float's bit pattern, which orders non-negative floats
    as integers (31 counting passes, no sort)."""
    bits = jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)

    def halve(_, lohi):
        lo, hi = lohi                       # count(bits >= lo) >= k > count(>= hi)
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits >= mid) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 31, halve,
                              (jnp.int32(0), jnp.int32(2 ** 31 - 1)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32).astype(mag.dtype)


def _topk(leaf, gamma: float, min_leaf_size: int):
    """Keep the ``round(gamma * n)`` entries of largest magnitude (ties at
    the threshold all kept); leaves under ``min_leaf_size`` pass whole."""
    n = leaf.size
    if n < min_leaf_size or gamma >= 1.0:
        return leaf
    k = max(1, int(round(gamma * n)))
    thresh = _kth_largest(jnp.abs(leaf), k)
    return jnp.where(jnp.abs(leaf) >= thresh, leaf, jnp.zeros_like(leaf))


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 7, 8))
def _client_block(loss, params, batches, lr, epochs: int, gamma: float,
                  min_leaf_size: int, fault: str | None, dtype: str):
    """Local SGD, delta and mask for a block of clients (leading axis of
    ``batches``).  Returns ``(masked deltas, mean losses)``."""
    dt = jnp.dtype(dtype)

    def one(client_batches):
        p0 = jax.tree.map(lambda x: x.astype(dt), params)

        def step(p, batch):
            if fault == "half_batch":
                batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
            value, g = jax.value_and_grad(loss)(p, batch)
            return jax.tree.map(lambda a, b: a - (lr * b).astype(dt), p, g), value

        def epoch(p, _):
            p, losses = jax.lax.scan(step, p, client_batches)
            return p, jnp.mean(losses)

        p, losses = jax.lax.scan(epoch, p0, None, length=epochs)
        delta = jax.tree.map(jnp.subtract, p, p0)
        masked = jax.tree.map(lambda d: _topk(d, gamma, min_leaf_size), delta)
        if fault == "index_permuted":
            masked = jax.tree.map(
                lambda d: jnp.roll(d.ravel(), 1).reshape(d.shape), masked)
        return masked, jnp.mean(losses)

    batches = jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, batches)
    return jax.vmap(one)(batches)


def run_rounds(loss, params0, client_batches, n_samples, ids_per_round, *,
               lr: float, epochs: int, gamma: float, min_leaf_size: int,
               block: int, dtype: str = "float32", fault: str | None = None,
               precision: str | None = "highest"):
    """Run the rounds whose participants ``ids_per_round`` lists, from
    ``params0``.  Clients are computed ``block`` at a time.  Returns
    ``(losses per round, params after each round)``.

    ``dtype`` is the precision of the whole computation (parameters,
    inputs, updates, aggregation); ``fault`` plants a known fault for the
    control readings: ``"half_batch"`` (each local step sees half its
    batch), ``"upload_altered"`` (the first participant's upload is
    negated) or ``"index_permuted"`` (every upload's values move one
    index along each flattened leaf).
    """
    dt = jnp.dtype(dtype)
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    params = jax.tree.map(lambda x: x.astype(dt), params0)
    losses, trail = [], []
    n_samples = np.asarray(n_samples, np.float64)
    with ctx:
        for ids in ids_per_round:
            w = n_samples[ids] / n_samples[ids].sum()
            acc = jax.tree.map(jnp.zeros_like, params)
            loss_sum = 0.0
            for s in range(0, len(ids), block):
                part = np.asarray(ids[s:s + block])
                rows = jax.tree.map(lambda a: jnp.take(a, part, axis=0),
                                    client_batches)
                masked, lv = _client_block(loss, params, rows, lr, epochs,
                                           gamma, min_leaf_size, fault, dtype)
                wb = jnp.asarray(w[s:s + block], dt)
                if fault == "upload_altered" and s == 0:
                    wb = wb.at[0].multiply(-1)
                acc = jax.tree.map(
                    lambda a, d: a + jnp.tensordot(wb, d, axes=(0, 0)),
                    acc, masked)
                loss_sum += float(jnp.sum(lv))
            params = jax.tree.map(jnp.add, params, acc)
            losses.append(loss_sum / len(ids))
            trail.append(params)
    return losses, trail


# ---- the comparison -------------------------------------------------------------
def _leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.linalg.norm(x.astype(jnp.float32).ravel()))
                       for x in jax.tree.leaves(tree)])


def _sub(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


def norm_gaps(prog_change, ref_change, keep: np.ndarray) -> np.ndarray:
    """Each leaf's ``| |p| - |r| | / max(|r|, median |r|)``, 0 where
    ``keep`` leaves it out."""
    p, r = _leaf_norms(prog_change), _leaf_norms(ref_change)
    floor = np.maximum(r, np.median(r[keep]))
    return np.where(keep, np.abs(p - r) / floor, 0.0)


def diff_gaps(prog_change, ref_change, keep: np.ndarray) -> np.ndarray:
    """Each leaf's ``|p - r| / max(|r|, median |r|)``, 0 where ``keep``
    leaves it out."""
    d = _leaf_norms(_sub(prog_change, ref_change))
    r = _leaf_norms(ref_change)
    floor = np.maximum(r, np.median(r[keep]))
    return np.where(keep, d / floor, 0.0)


def leaf_names(tree) -> list:
    """``a/b``-style paths of the leaves of ``tree``."""
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def readings(prog: dict, ref_losses, ref_trail, ref_params0) -> dict:
    """The compared numbers.  ``prog`` holds the program's ``losses`` of
    rounds 1-3 and its parameters ``p0``, ``p1`` and ``p3``."""
    ref_update1 = _sub(ref_trail[0], ref_params0)
    norms1 = _leaf_norms(ref_update1)
    keep = norms1 >= FLOOR_SHARE * np.median(norms1)
    lp = np.asarray(prog["losses"], np.float64)
    lr_ = np.asarray(ref_losses, np.float64)
    gaps = np.abs(lp - lr_) / np.abs(lr_)
    if not np.all(np.isfinite(lp)):
        gaps[:] = np.inf
    prog_update1 = _sub(prog["p1"], prog["p0"])
    g1 = norm_gaps(prog_update1, ref_update1, keep)
    d1 = diff_gaps(prog_update1, ref_update1, keep)
    g3 = norm_gaps(_sub(prog["p3"], prog["p0"]),
                   _sub(ref_trail[2], ref_params0), keep)
    names = leaf_names(ref_params0)
    return {
        "loss_gap": float(gaps.max()),
        "loss1_gap": float(gaps[0]),
        "update1_gap": float(g1.max()),
        "update1_diff": float(d1.max()),
        "change3_gap": float(g3.max()),
        "change3_median_gap": float(np.median(g3[keep])),
        "detail": {
            "leaves_compared": int(keep.sum()),
            "update1_worst_leaf": names[int(g1.argmax())],
            "update1_diff_worst_leaf": names[int(d1.argmax())],
            "change3_worst_leaf": names[int(g3.argmax())],
            "losses": [float(x) for x in prog["losses"]],
            "ref_losses": [float(x) for x in ref_losses],
        },
    }
