"""GRU language model with tied embeddings (the paper's WikiText-2 model).

The program side is ``repro.models.init_gru_lm`` / ``gru_lm_loss``.  The
plain reference below restates the same model in ``jax.numpy`` and
imports nothing of the program: the GRU cell of Cho et al.
(arXiv:1406.1078) with the reset gate applied to the state before the
candidate's matmul, a ``d_hidden -> d_embed`` projection, and the output
logits against the tied embedding table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(key, cfg: dict):
    """The program's parameters, built by its own init."""
    from repro.models import init_gru_lm
    return init_gru_lm(key, cfg["vocab_size"], cfg["d_embed"],
                       cfg["d_hidden"], tied=cfg["tied"])


def loss_fn(cfg: dict):
    """The program's loss, as the round calls it: ``loss(params, (x, y))``."""
    from repro.models import gru_lm_loss
    return gru_lm_loss


def token_flops(cfg: dict) -> int:
    """Forward plus backward FLOPs of one token, from shapes, no recompute.

    Forward: the three gate matmuls over ``[x, h]`` (``2 (de + d) d`` each),
    the projection (``2 d de``) and the tied output logits (``2 de V``).
    Backward: twice the forward, since every matmul here needs both its
    input and its weight gradient (the embedding is trained).  Elementwise
    work and the softmax are not counted.
    """
    de, d, v = cfg["d_embed"], cfg["d_hidden"], cfg["vocab_size"]
    fwd = 3 * 2 * (de + d) * d + 2 * d * de + 2 * de * v
    return 3 * fwd


def example_flops(cfg: dict, traffic: dict) -> int:
    """FLOPs of one batch row: a ``seq_len``-token window."""
    return traffic["seq_len"] * token_flops(cfg)


# ---- plain reference -------------------------------------------------------
def _truncated(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def ref_init(key, cfg: dict) -> dict:
    """The initial parameters from the seed, drawn as the program draws
    them (same keys, same distributions), in float32."""
    de, d, v = cfg["d_embed"], cfg["d_hidden"], cfg["vocab_size"]
    ks = jax.random.split(key, 8)
    p = {
        "embed": de ** -0.5 * jax.random.normal(ks[0], (v, de), jnp.float32),
        "wz": _truncated(ks[1], (de + d, d), (de + d) ** -0.5),
        "wr": _truncated(ks[2], (de + d, d), (de + d) ** -0.5),
        "wn": _truncated(ks[3], (de + d, d), (de + d) ** -0.5),
        "bz": jnp.zeros((d,), jnp.float32),
        "br": jnp.zeros((d,), jnp.float32),
        "bn": jnp.zeros((d,), jnp.float32),
        "proj": _truncated(ks[4], (d, de), d ** -0.5),
    }
    if not cfg["tied"]:
        p["head"] = _truncated(ks[5], (de, v), de ** -0.5)
    return p


def ref_loss(params: dict, batch) -> jax.Array:
    """Mean next-token cross-entropy of a ``(B, T)`` batch, computed in the
    parameters' dtype."""
    x, y = batch
    dt = params["embed"].dtype
    e = params["embed"][x]                                  # (B, T, de)
    h = jnp.zeros((x.shape[0], params["bz"].shape[0]), dt)

    def cell(h, xt):
        z = jax.nn.sigmoid(jnp.concatenate([xt, h], -1) @ params["wz"]
                           + params["bz"])
        r = jax.nn.sigmoid(jnp.concatenate([xt, h], -1) @ params["wr"]
                           + params["br"])
        n = jnp.tanh(jnp.concatenate([xt, r * h], -1) @ params["wn"]
                     + params["bn"])
        h = (1 - z) * n + z * h
        return h, h

    _, hs = jax.lax.scan(cell, h, jnp.swapaxes(e, 0, 1))
    out = jnp.swapaxes(hs, 0, 1) @ params["proj"]           # (B, T, de)
    head = params["head"] if "head" in params else params["embed"].T
    logits = (out @ head).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, y[..., None], axis=-1))
