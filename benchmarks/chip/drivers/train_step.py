"""Driver: the grown model's training steps.

The window drives the stage-training step ``TrajectoryRunner`` builds —
``repro.training.make_train_step`` (masked-LM loss with per-layer remat,
global-norm clipping, AdamW on the warm-up-then-cosine schedule) jitted by
``pjit_train_step`` on a one-device mesh — over a pool of distinct batches,
one step after another. The host keeps one step in flight ahead of the
device (it waits for the loss of step i - 1 before it dispatches step
i + 1), and the window ends when the last step's outputs are ready.
``train_tokens_per_s`` is every token of every step over that window.

Set-up makes the weights and AdamW state of a run under way on the device
from the seed, builds the jitted step, and drives it through its first
steps (which compiles it): their losses, the gradient of the first step
(worked out from the first moment it leaves, m1 = b1 m0 + (1 - b1) g) and
the parameters' change over the first steps are what the check compares
with the plain reference following the same steps. The window goes on from
the state those steps leave.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmarks.chip.lib import reference as R
from benchmarks.chip.lib import tokens, weights
from benchmarks.chip.lib.programs import program_config

SALT_PARAMS, SALT_MOMENTS, SALT_DATA = 11, 12, 13
TRAINING = True         # planted faults bound the limits (tools/limits.py)
F32 = jnp.float32


def _norms(tree) -> List[float]:
    return [float(x) for x in jax.device_get(
        [jnp.linalg.norm(x.astype(F32).ravel()) for x in
         jax.tree.leaves(tree)])]


def make_inputs(ctx):
    """Weights, AdamW moments and the batch pool, from the seed."""
    cfg, tr = ctx.config, ctx.traffic
    m = cfg[tr["model"]]
    params = weights.params_on_device(ctx.key(SALT_PARAMS), m,
                                      weights.DTYPES[cfg["dtype"]])
    mom, vel = weights.moments_on_device(ctx.key(SALT_MOMENTS), params)
    seed = ctx.np_seed(SALT_DATA)
    pool = [jax.device_put(tokens.mlm_batch(seed, k, tr["batch"], tr["seq"],
                                            m["vocab_size"]))
            for k in range(tr["pool"])]
    return params, mom, vel, pool


def train_config(tr: Dict):
    from repro.configs.base import TrainConfig
    return TrainConfig(steps=tr["total_steps"], warmup_steps=tr["warmup_steps"],
                       lr=tr["lr"], seq_len=tr["seq"],
                       global_batch=tr["batch"])


def setup(ctx):
    from repro.launch.mesh import make_host_mesh
    from repro.optim import AdamWState
    from repro.training import make_train_step, pjit_train_step
    tr = ctx.traffic
    cfg = program_config(ctx.config, tr["model"], ctx.log)
    params, mom, vel, pool = make_inputs(ctx)
    opt = AdamWState(m=mom, v=vel, count=jnp.asarray(tr["start_step"],
                                                     jnp.int32))
    jax.block_until_ready((params, opt, pool))
    ctx.mark("init")
    step_fn = make_train_step(cfg, train_config(tr))
    jstep, _, _ = pjit_train_step(step_fn, params, pool[0],
                                  make_host_mesh(1))
    # the first steps: compile, warm, and leave what the check compares
    p, o, losses = params, opt, []
    g1 = upd = None
    for i in range(tr["first_steps"]):
        with ctx.span("bench.train_step"):
            p, o, met = jstep(p, o, pool[i], jnp.asarray(tr["start_step"] + i))
        losses.append(float(met["loss"]))
        if i == 0:
            b1 = train_config(tr).b1
            g1 = _norms(jax.tree.map(lambda a, b: (a - b1 * b) / (1 - b1),
                                     o.m, mom))
    upd = _norms(jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32),
                              p, params))
    del params, mom, vel, opt
    return {"jstep": jstep, "params": p, "opt": o, "pool": pool,
            "first": {"losses": losses, "grad": g1, "update": upd}}


def window(ctx, state) -> Dict:
    tr = ctx.traffic
    jstep, pool = state["jstep"], state["pool"]
    p, o = state["params"], state["opt"]
    step = tr["start_step"] + tr["first_steps"]
    steps, prev, t0 = 0, None, time.perf_counter()
    while True:
        with ctx.span("bench.train_step"):
            p, o, met = jstep(p, o, pool[(step + steps) % len(pool)],
                              jnp.asarray(step + steps))
        steps += 1
        if prev is not None:
            with ctx.span("bench.wait"):
                prev.block_until_ready()
        prev = met["loss"]
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready((p, o, met))
    window_s = time.perf_counter() - t0
    loss = float(met["loss"])
    state["params"], state["opt"] = p, o
    tokens_ = steps * tr["batch"] * tr["seq"]
    return {"window_s": window_s, "steps": steps, "tokens": tokens_,
            "train_tokens_per_s": tokens_ / window_s, "last_loss": loss,
            "attempted": steps, "failed": 0 if math.isfinite(loss) else 1}


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def reference_steps(ctx, pr: R.Precision = R.REF) -> Dict:
    """The plain reference following the first steps from the same weights,
    moments and batches: losses, first clipped gradient, parameter change
    (parameters kept in the configuration's dtype, as the program keeps
    them)."""
    cfg, tr = ctx.config, ctx.traffic
    m = cfg[tr["model"]]
    dtype = weights.DTYPES[cfg["dtype"]]
    tc = train_config(tr)
    params, mom, vel, pool = make_inputs(ctx)

    @jax.jit
    def grad(p, batch):
        loss, g = jax.value_and_grad(R.mlm_loss)(p, m, batch, pr)
        return loss, R.clip_global(g, tc.grad_clip)

    @jax.jit
    def update(p, mo, ve, g, lr, count):
        return R.adamw(p, mo, ve, count, g, lr=lr, b1=tc.b1, b2=tc.b2,
                       weight_decay=tc.weight_decay, store_dtype=dtype)

    p, mo, ve = params, mom, vel
    count = jnp.asarray(tr["start_step"], F32)
    losses, g1 = [], None
    for i in range(tr["first_steps"]):
        loss, g = grad(p, pool[i])
        losses.append(float(loss))
        if i == 0:
            g1 = _norms(g)
        lr = R.warmup_cosine(tr["start_step"] + i, base_lr=tc.lr,
                             warmup_steps=tc.warmup_steps,
                             total_steps=tc.steps, end_frac=tc.end_lr_frac)
        p, mo, ve, count = update(p, mo, ve, g, jnp.asarray(lr, F32), count)
    upd = _norms(jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32),
                              p, params))
    return {"losses": losses, "grad": g1, "update": upd}


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    med = sorted(ref["grad"])[len(ref["grad"]) // 2]
    keep = [g >= 1e-3 * med for g in ref["grad"]]
    return {"loss_gap": loss_gap,
            "grad_gap": R.worst_norm_gap(got["grad"], ref["grad"], keep),
            "update_gap": R.worst_norm_gap(got["update"], ref["update"],
                                           keep)}


def readings(ctx, state, control: bool = True
             ) -> Dict[str, Dict[str, float]]:
    """The compared numbers of the program's first steps and, with
    ``control``, of the control (the reference in the program's place, in
    8-bit floats)."""
    got = state.pop("first")
    state.clear()
    ref = reference_steps(ctx)
    out = {"program": compare(got, ref)}
    if control:
        out["control"] = compare(reference_steps(ctx, R.CONTROL), ref)
    return out


def check(ctx, state):
    got = state.pop("first")
    state.clear()
    return ctx.checks(compare(got, reference_steps(ctx)))
