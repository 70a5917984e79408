"""End-to-end DeCaPH training driver (pod-scale path).

Runs the SPMD DeCaPH train step on real devices (CPU here; the mesh shape
adapts to the available device count).  Hospitals map onto the data axis —
each data shard's examples come from one silo's stream — and the gradient
all-reduce is the secure-aggregation sum (DESIGN.md §3).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 100 --batch 8 --seq 256 [--scale 0.1] [--no-dp]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCHITECTURES, get_config, get_smoke_config
from repro.configs.base import dense_stack
from repro.core.accountant import RDPAccountant
from repro.data import make_lm_stream
from repro.launch import sharding as sh
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import ShardedProgram
from repro.models import transformer as tf
from repro.models.layers import activation_sharding
from repro.core import dp as dp_lib
from repro.optim import get_optimizer


def scaled_config(arch: str, scale: str):
    if scale == "full":
        return get_config(arch)
    if scale == "smoke":
        return get_smoke_config(arch)
    if scale == "100m":
        # ~100M-param member of the arch family for the e2e example
        cfg = get_smoke_config(arch)
        return cfg.replace(
            d_model=512, n_heads=8, n_kv_heads=4, head_dim=64, d_ff=1536,
            vocab_size=8192,
            stack=dense_stack(12) if cfg.arch_type == "dense" else cfg.stack,
            n_layers=12 if cfg.arch_type == "dense" else cfg.n_layers,
        )
    raise ValueError(scale)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list(ARCHITECTURES), default="smollm-360m")
    p.add_argument("--scale", default="smoke", choices=["full", "smoke", "100m"])
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--no-dp", action="store_true")
    p.add_argument("--clip", type=float, default=1.0)
    p.add_argument("--sigma", type=float, default=0.8)
    p.add_argument("--eps-budget", type=float, default=None)
    p.add_argument("--n-silos", type=int, default=4,
                   help="synthetic hospitals feeding the data shards")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--checkpoint", default=None)
    args = p.parse_args()

    cfg = scaled_config(args.arch, args.scale)
    if args.lr:
        cfg = cfg.replace(lr=args.lr)
    mesh = make_host_mesh()
    policy = sh.ShardingPolicy()
    print(f"mesh={dict(mesh.shape)} arch={args.arch} scale={args.scale} "
          f"dp={'off' if args.no_dp else 'on'}")

    key = jax.random.key(0)
    params = tf.init(cfg, key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"params: {n_params/1e6:.1f}M")
    opt = get_optimizer(cfg.optimizer, cfg.lr)
    opt_state = opt.init(params)

    pspecs = sh.param_specs(params, mesh, policy)
    params = jax.device_put(params, pspecs)
    rules = sh.activation_rules(mesh, policy, global_batch=args.batch)
    constrain = lambda tree: jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, pspecs
    )

    # Every silo contributes batch/n_silos examples per round (one DeCaPH
    # round == one step); streams differ per silo (covariate shift via seed).
    streams = [
        make_lm_stream(cfg.vocab_size, args.seq, seed=17 * i + 1)
        for i in range(args.n_silos)
    ]
    acct = None
    if not args.no_dp:
        acct = RDPAccountant(
            sampling_rate=min(1.0, args.batch / (args.batch * 50)),
            noise_multiplier=args.sigma, delta=1e-5,
        )

    def train_step(params, opt_state, batch, rng):
        with activation_sharding(rules):
            if args.no_dp:
                loss, grads = jax.value_and_grad(
                    lambda p: tf.loss_fn(cfg, p, batch)
                )(params)
            else:
                g_sum, loss = dp_lib.per_example_clipped_grad_sum(
                    lambda p, ex: tf.per_example_loss_fn(cfg, p, ex),
                    params, batch, clip_norm=args.clip,
                    microbatch_size=max(1, args.batch // 2),
                    constrain_grads=constrain,
                )
                g_sum = dp_lib.tree_add_noise(
                    g_sum, rng, clip_norm=args.clip,
                    noise_multiplier=args.sigma, n_shares=1,
                )
                grads = jax.tree_util.tree_map(
                    lambda x: x / float(args.batch), g_sum
                )
            new_p, new_o = opt.update(grads, opt_state, params)
            return new_p, new_o, loss

    step_jit = jax.jit(train_step, donate_argnums=(0, 1))

    t0 = time.time()
    for step in range(args.steps):
        per_silo = max(1, args.batch // args.n_silos)
        parts = [s.batch(step, per_silo) for s in streams]
        batch = {
            k: jnp.asarray(np.concatenate([p[k] for p in parts]))
            for k in parts[0]
        }
        rng = jax.random.fold_in(key, 1000 + step)
        params, opt_state, loss = step_jit(params, opt_state, batch, rng)
        if acct:
            acct.step()
        if step % args.log_every == 0 or step == args.steps - 1:
            eps = acct.epsilon() if acct else 0.0
            print(f"step {step:4d} loss {float(loss):.4f} "
                  f"eps {eps:.3f} ({time.time()-t0:.1f}s)")
        if acct and args.eps_budget and acct.epsilon() > args.eps_budget:
            print(f"privacy budget {args.eps_budget} reached at step {step}")
            break
    if args.checkpoint:
        save_checkpoint(args.checkpoint, jax.device_get(params), step=args.steps)
        print("checkpoint written:", args.checkpoint)


if __name__ == "__main__":
    main()
