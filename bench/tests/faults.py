"""Toy sizes of the cells, and faults planted under the timed path, for
the tests of ``correct``.

The toy sizes keep each cell's path (model, masking backend, codec,
cohort bucketing) and shrink its widths and fleet so that a run fits a
CPU test; the limits are the cell's own.
"""

import jax
import jax.numpy as jnp


_TOY_GRU = {
    "config": {"vocab_size": 512},
    "traffic": {"num_clients": 8, "train_tokens": 8 * 3 * 4 * 32 + 1}}
TOY = {"gru_lm.topk_fused": _TOY_GRU, "gru_lm.dense": _TOY_GRU}


def run_toy(cell: str, seed: int = 2**31 + 11):
    """One run of ``cell`` at its toy size on the CPU, chip check skipped."""
    from bench import run
    return run.run_cell(cell, seed, 0.5, False, require_tpu=False,
                        overrides=TOY[cell])


def plant(monkeypatch, fault: str) -> None:
    """Break the program under the timed path.

    * ``state_unchanged``: the round returns the global parameters it was
      given;
    * ``half_batch``: each local step sees half of its batch and takes the
      mean over it;
    * ``upload_altered``: the first cohort row's upload is negated where
      the client produces it;
    * ``index_permuted``: every upload's values move one index along each
      flattened leaf, as a decode that scatters to the wrong indices would
      put them.
    """
    from repro.core import client, federated
    from repro.core.server import FederatedServer

    if fault == "state_unchanged":
        build = FederatedServer._round_program

        def frozen(self, bucket, seg_len):
            fn = build(self, bucket, seg_len)

            def round_fn(*args):
                out = fn(*args)
                return (args[0],) + tuple(out[1:])
            return round_fn

        monkeypatch.setattr(FederatedServer, "_round_program", frozen)
    elif fault == "half_batch":
        sgd = client.local_sgd

        def half(loss_fn, params, batches, cfg):
            b = jax.tree.leaves(batches)[0].shape[1]
            return sgd(loss_fn, params,
                       jax.tree.map(lambda a: a[:, : b // 2], batches), cfg)

        monkeypatch.setattr(client, "local_sgd", half)
    elif fault == "upload_altered":
        update = federated.stacked_client_update

        def altered(*args, **kw):
            uploads, *rest = update(*args, **kw)
            uploads = jax.tree.map(lambda u: u.at[0].multiply(-1), uploads)
            return (uploads, *rest)

        monkeypatch.setattr(federated, "stacked_client_update", altered)
    elif fault == "index_permuted":
        update = federated.stacked_client_update

        def permuted(*args, **kw):
            uploads, *rest = update(*args, **kw)
            uploads = jax.tree.map(
                lambda u: jnp.roll(u.reshape(u.shape[0], -1), 1, axis=1)
                .reshape(u.shape), uploads)
            return (uploads, *rest)

        monkeypatch.setattr(federated, "stacked_client_update", permuted)
    else:
        raise ValueError(fault)


def control_readings(cell: str, seed: int = 2**31 + 11) -> dict:
    """The compared numbers of the control: the reference computed in
    bfloat16 put in the program's place, against the reference."""
    from bench import calibrate
    (r,) = calibrate.readings(cell, seed, ["control"], TOY[cell])
    return r


FAULTS = ("state_unchanged", "half_batch", "upload_altered",
          "index_permuted")
