"""Client text from the seed, built on the device in one jitted call.

A copy of ``repro.data.markov_text`` + ``partition_text`` for the
benchmark, so that a later change to the program's data code does not move
the yardstick.  The same process: an order-2 Markov chain over a
Zipf-biased vocabulary (each of ``CONTEXTS`` hashed contexts admits
``BRANCHING`` next tokens with weights 1/j), chopped into
``seq_len + 1``-token windows that are shuffled and dealt IID to the
clients.  The repo's version runs one
Python step per token; here ``STREAMS`` chains run side by side in one
``lax.scan``, and the windows are cut from their concatenation.

Traffic parameters (the cell's ``traffic`` entry): ``num_clients``,
``train_tokens``, ``batch`` and ``seq_len``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BRANCHING = 8
CONTEXTS = 4096
STREAMS = 256


@functools.partial(jax.jit, static_argnums=(1, 2))
def _generate(key, vocab: int, shape: tuple):
    num_clients, train_tokens, batch, seq_len = shape
    k_tab, k_start, k_choice, k_perm = jax.random.split(key, 4)
    nexts = jnp.clip((vocab * jax.random.uniform(
        k_tab, (CONTEXTS, BRANCHING)) ** 2.5).astype(jnp.int32), 0, vocab - 1)
    logits = -jnp.log(jnp.arange(1, BRANCHING + 1, dtype=jnp.float32))
    length = -(-train_tokens // STREAMS)
    start = jax.random.randint(k_start, (2, STREAMS), 0, vocab, jnp.int32)
    choice = jax.random.categorical(k_choice, logits,
                                    shape=(length - 2, STREAMS))

    def step(carry, c):
        t2, t1 = carry
        t = nexts[(t2 * 31 + t1 * 7) % CONTEXTS, c]
        return (t1, t), t

    _, toks = jax.lax.scan(step, (start[0], start[1]), choice)
    stream = jnp.concatenate([start, toks]).T.reshape(-1)[:train_tokens]

    num_win = (train_tokens - 1) // seq_len
    per_client = (num_win // num_clients // batch) * batch
    win = jax.random.permutation(k_perm, num_win)[:per_client * num_clients]
    idx = win[:, None] * seq_len + jnp.arange(seq_len + 1)[None, :]
    wins = stream[idx].reshape(num_clients, per_client // batch, batch,
                               seq_len + 1)
    n = jnp.full((num_clients,), per_client, jnp.float32)
    return (wins[..., :-1], wins[..., 1:]), n


def generate(key, cfg: dict, traffic: dict):
    """``((inputs, targets), n_samples)``: token windows of shape
    ``(num_clients, num_batches, batch, seq_len)`` and each client's
    window count, on the default device."""
    shape = (traffic["num_clients"], traffic["train_tokens"],
             traffic["batch"], traffic["seq_len"])
    per_client = ((traffic["train_tokens"] - 1) // traffic["seq_len"]
                  // traffic["num_clients"] // traffic["batch"])
    if per_client < 1 or traffic["train_tokens"] < 3 * STREAMS:
        raise ValueError("not enough tokens for one batch per client")
    return _generate(key, cfg["vocab_size"], shape)
