"""Quickstart: the LiGO pipeline in one file.

Pretrains a small transformer on the synthetic corpus, *learns* the growth
operator with 50 SGD steps (paper §3.2), grows to a 2× deeper & wider model,
and compares the grown initialisation against from-scratch + StackBERT before
a short finetune.

    PYTHONPATH=src python examples/quickstart.py
"""
import itertools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import grow
from repro.data import batch_for_step, optimal_loss
from repro.models import init_params, loss_fn
from repro.optim import adamw_init
from repro.training import make_train_step

SMALL = ModelConfig(name="qs-small", family="dense", n_layers=2, d_model=64,
                    n_heads=4, n_kv_heads=4, d_head=16, d_ff=256,
                    vocab_size=256, rope="rope", act="gelu", norm="layer",
                    dtype="float32", objective="clm", max_seq=128)
BIG = SMALL.scaled(name="qs-big", n_layers=4, d_model=128, n_heads=8,
                   d_head=16, d_ff=512)

BATCH, SEQ = 32, 64


def batches(cfg, start=0, seed=0):
    for s in itertools.count(start):
        yield {k: jnp.asarray(v)
               for k, v in batch_for_step(cfg, s, BATCH, SEQ, seed=seed).items()}


def train(cfg, params, steps, lr=3e-3):
    tcfg = TrainConfig(steps=steps, warmup_steps=max(steps // 10, 1), lr=lr)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, tcfg))
    it = batches(cfg, seed=1)
    for i in range(steps):
        params, opt, m = step(params, opt, next(it), jnp.asarray(i))
    return params, float(m["total"])


def eval_loss(cfg, params):
    b = next(batches(cfg, start=10_000_000, seed=99))
    return float(loss_fn(params, cfg, b)[0])


def main():
    print(f"corpus entropy floor ≈ {optimal_loss(256):.3f} nats")
    print("1) pretraining the small model (2L×64d)...")
    small = init_params(SMALL, jax.random.PRNGKey(0))
    small, loss = train(SMALL, small, 300)
    print(f"   small model loss: {loss:.3f}")

    print("2) growing to 4L×128d ...")
    inits = {}
    inits["scratch"] = init_params(BIG, jax.random.PRNGKey(1))
    inits["stackbert"], _ = grow(small, SMALL, BIG, method="bert2bert",
                                 key=jax.random.PRNGKey(2))
    inits["ligo"], info = grow(small, SMALL, BIG, method="ligo",
                               key=jax.random.PRNGKey(3),
                               data_it=batches(SMALL, 500_000),
                               ligo_steps=50, ligo_lr=3e-3)
    print(f"   LiGO operator loss: {info['ligo_losses'][0]:.3f} -> "
          f"{info['ligo_losses'][-1]:.3f} (50 steps)")

    print("3) initial big-model loss (before any big-model training):")
    for name, p in inits.items():
        print(f"   {name:10s} {eval_loss(BIG, p):.3f}")

    print("4) finetuning each for 100 steps:")
    for name, p in inits.items():
        _, l = train(BIG, p, 100)
        print(f"   {name:10s} {l:.3f}")
    print("LiGO should start (and stay) ahead.")


if __name__ == "__main__":
    main()
