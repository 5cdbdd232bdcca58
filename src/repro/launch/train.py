"""Pod-scale training driver.

Two modes:
* ``--mode standard``  — plain distributed LM training (AdamW, FSDP x TP).
* ``--mode federated`` — the paper's technique: federated rounds with
  dynamic sampling + selective masking (launch/fedtrain.py), clients mapped
  onto the mesh's client axis.

On this CPU container you run it with a tiny mesh / reduced arch, e.g.:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --mesh 1x1 --steps 10 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --reduced \
      --mode federated --rounds 5 --clients 4 --gamma 0.2 --beta 0.1

On a real pod the same script runs with ``--mesh 16x16`` (the production
mesh) and the full arch id.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs import get_arch
from repro.core.sampling import DynamicSampling, StaticSampling
from repro.data.synthetic import markov_text
from repro.launch import shardings as sh
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.fedtrain import FedPodConfig, make_fed_round
from repro.launch.mesh import make_mesh
from repro.models import transformer as tr


def make_mesh_arg(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    names = {1: ("model",), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, names)


def synth_batches(cfg, batch, seq, steps, seed=0):
    data = markov_text(num_train=(batch * seq + 1) * steps + 1,
                       vocab_size=min(cfg.vocab_size, 512), seed=seed)
    toks = data.train_tokens
    out = []
    for i in range(steps):
        w = toks[i * batch * seq:(i + 1) * batch * seq + 1]
        x = w[:-1].reshape(batch, seq) % cfg.vocab_size
        y = w[1:].reshape(batch, seq) % cfg.vocab_size
        out.append({"tokens": jnp.asarray(x), "labels": jnp.asarray(y)})
    return out


def run_standard(args, cfg, mesh):
    step = steps_lib.make_train_step(cfg, learning_rate=args.lr)
    params = tr.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = step.optimizer.init(params)
    psh = sh.params_shardings(params, mesh)
    osh = sh.params_shardings_like(opt_state, psh, mesh)
    batches = synth_batches(cfg, args.batch, args.seq, args.steps, args.seed)
    bsh = sh.batch_shardings(batches[0], mesh)
    fn = jax.jit(step, in_shardings=(psh, osh, bsh),
                 out_shardings=(psh, osh, None), donate_argnums=(0, 1))
    with mesh:
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(opt_state, osh)
        for i, b in enumerate(batches):
            t0 = time.time()
            params, opt_state, m = fn(params, opt_state,
                                      jax.device_put(b, bsh))
            print(f"step {i}: loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"dt={time.time() - t0:.2f}s", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, args.steps, params)
    return params


def run_federated(args, cfg, mesh):
    C = args.clients
    fed_cfg = FedPodConfig(num_clients=C, local_steps=args.local_steps,
                           learning_rate=args.lr, gamma=args.gamma,
                           masking=args.masking)
    schedule = (DynamicSampling(initial_rate=args.init_rate, beta=args.beta)
                if args.beta > 0 else StaticSampling(initial_rate=args.init_rate))
    fed_round = make_fed_round(cfg, fed_cfg)

    params = tr.init_params(jax.random.PRNGKey(args.seed), cfg)
    data = synth_batches(cfg, C * args.batch, args.seq,
                         args.local_steps * args.rounds, args.seed)
    n_samples = jnp.ones((C,), jnp.float32)
    key = jax.random.PRNGKey(args.seed + 1)
    fn = jax.jit(fed_round)

    with mesh:
        for t in range(1, args.rounds + 1):
            key, k_part, k_mask = jax.random.split(key, 3)
            from repro.core.sampling import participation_mask
            part = participation_mask(k_part, schedule, t, C)
            sl = data[(t - 1) * args.local_steps: t * args.local_steps]
            toks = jnp.stack([b["tokens"] for b in sl], 0)   # (S, C*b, T)
            labs = jnp.stack([b["labels"] for b in sl], 0)
            S = toks.shape[0]
            batches = {
                "tokens": toks.reshape(S, C, args.batch, args.seq)
                .transpose(1, 0, 2, 3),
                "labels": labs.reshape(S, C, args.batch, args.seq)
                .transpose(1, 0, 2, 3),
            }
            t0 = time.time()
            params, m = fn(params, batches, n_samples, part, k_mask)
            print(f"round {t}: sampled={int(m['num_sampled'])}/{C} "
                  f"loss={float(m['mean_loss']):.4f} "
                  f"transport={float(m['num_sampled']) * fed_cfg.gamma:.2f} "
                  f"model-units dt={time.time() - t0:.2f}s", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, args.rounds, params)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="standard",
                    choices=["standard", "federated"])
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--gamma", type=float, default=0.1)
    ap.add_argument("--beta", type=float, default=0.0)
    ap.add_argument("--init-rate", type=float, default=1.0)
    ap.add_argument("--masking", default="selective",
                    choices=["selective", "random", "none"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_mesh_arg(args.mesh)
    if args.mode == "standard":
        run_standard(args, cfg, mesh)
    else:
        run_federated(args, cfg, mesh)


if __name__ == "__main__":
    main()
