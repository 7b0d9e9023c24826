"""Driver: whole LiGO hops, back to back.

The window calls ``repro.core.grow.grow(method="ligo")`` again and again
with the small model's weights and AdamW state: each call is one hop — the
LiGO phase (SGD with momentum on the growth operator, through the grown
model's masked-LM loss), the grown parameters, and the AdamW moments grown
through the trained operator. ``hop_s`` is the window over the whole hops
it completed.

Set-up makes the small model's weights and moments on the device from the
seed and a pool of distinct batches (one per LiGO step), then makes the
first hop through the same call with ``first_steps`` steps: that compiles
every program the window's hops run (the chunk program, the growth of the
parameters and of both moments), and its outputs — the losses of its steps,
the trained operator, the grown parameters and moments — are what the check
compares with the plain reference following the same steps.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks.chip.lib import reference as R
from benchmarks.chip.lib import tokens, weights
from benchmarks.chip.lib.programs import program_configs

SALT_SMALL, SALT_MOMENTS, SALT_OP, SALT_DATA = 1, 2, 3, 4
TRAINING = True         # planted faults bound the limits (tools/limits.py)


def make_inputs(ctx) -> Dict:
    """Small weights, AdamW state and the batch pool, all from the seed."""
    from repro.optim import AdamWState
    cfg, tr = ctx.config, ctx.traffic
    dtype = weights.DTYPES[cfg["dtype"]]
    small = weights.params_on_device(ctx.key(SALT_SMALL), cfg["src"], dtype)
    m, v = weights.moments_on_device(ctx.key(SALT_MOMENTS), small)
    opt = AdamWState(m=m, v=v, count=jnp.asarray(tr["adam_count"],
                                                 jnp.int32))
    seed = ctx.np_seed(SALT_DATA)
    pool = [jax.device_put(tokens.mlm_batch(seed, k, tr["batch"], tr["seq"],
                                            cfg["src"]["vocab_size"]))
            for k in range(tr["ligo_steps"])]
    return {"small": small, "opt": opt, "pool": pool}


def hop(ctx, state, steps: int):
    """One whole hop through the program's ``grow``; waits for every
    output."""
    from repro.core.grow import grow
    tr = ctx.traffic
    big, info = grow(state["small"], state["cfg1"], state["cfg2"],
                     method="ligo", key=ctx.key(SALT_OP),
                     data_it=iter(state["pool"][:steps]), ligo_steps=steps,
                     ligo_lr=tr["ligo_lr"], ligo_momentum=tr["ligo_momentum"],
                     opt_state=state["opt"])
    jax.block_until_ready((big, info["opt_state"], info["operator"]))
    return big, info


def setup(ctx):
    cfg1, cfg2 = program_configs(ctx.config, ctx.log)
    state = make_inputs(ctx)
    state.update(cfg1=cfg1, cfg2=cfg2)
    jax.block_until_ready((state["small"], state["opt"], state["pool"]))
    ctx.mark("init")
    from repro.core.plan import plan_for
    plan = plan_for(cfg1, cfg2, state["small"])
    k, n = plan.kernel_groups()
    ctx.records["kernel_groups"] = kernel_groups(plan, ctx.config["dst"])
    ctx.log(f"plan: {k}/{n} groups on the fused kernels")
    with ctx.span("bench.grow"):
        big, info = hop(ctx, state, ctx.traffic["first_steps"])
    state["first"] = {"losses": info["ligo_losses"],
                      "op": info["operator"], "big": big,
                      "opt": info["opt_state"]}
    # one whole hop as the window makes them: the programs a hop of
    # ``ligo_steps`` runs beyond the first hop's (the losses of several
    # chunks) compile here and not in the window
    with ctx.span("bench.warm"):
        hop(ctx, state, ctx.traffic["ligo_steps"])
    return state


def kernel_groups(plan, dst: Dict) -> list:
    """Shapes of the plan's groups on the fused route, as the kernels see
    them: w (G, L2, L1), B (I, A), W (G, L1, E, A, Bd), bf16 W and P. The
    grown in-dimension I of each leaf follows the paper's tying (A^O = B_v
    grows the query space, A^{fc2} = B_fc1 the feed-forward one)."""
    d2, ff2 = dst["d_model"], dst["d_ff"]
    grown_in = {"wq": d2, "wk": d2, "wv": d2, "wo": d2, "mlp/w1": d2,
                "mlp/w2": ff2}
    out = []
    for g in plan.groups:
        if not g.kernel_ok:
            continue
        shape = tuple(g.shape)
        out.append({"G": len(g.paths), "L1": shape[0],
                    "L2": dst["n_layers"],
                    "E": shape[1] if len(shape) == 4 else 1,
                    "I": grown_in[g.paths[0]], "A": shape[-2],
                    "Bd": shape[-1], "itemsize": 2,
                    "paths": list(g.paths)})
    return out


def window(ctx, state) -> Dict:
    hops, t0 = 0, time.perf_counter()
    while True:
        with ctx.span("bench.hop"):
            big, info = hop(ctx, state, ctx.traffic["ligo_steps"])
        del big, info
        hops += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    return {"window_s": window_s, "hops": hops, "hop_s": window_s / hops,
            "attempted": hops, "failed": 0}


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def reference_hop(ctx, state, pr: R.Precision = R.REF) -> Dict:
    """The plain reference following the first hop's steps from the same
    weights, moments and batches: its losses, operator, grown parameters
    and grown moments."""
    cfg, tr = ctx.config, ctx.traffic
    src, dst = cfg["src"], cfg["dst"]
    small, opt = state["small"], state["opt"]

    # the small model is an argument: closed over, it would be folded
    # into the program as constants, which compiles for minutes
    @jax.jit
    def step(op, mom, small, batch):
        loss, g = jax.value_and_grad(R.ligo_loss)(op, small, src, dst,
                                                  batch, pr)
        op, mom = R.sgd_momentum(op, mom, g, lr=tr["ligo_lr"],
                                 momentum=tr["ligo_momentum"])
        return op, mom, loss

    op0 = R.init_operator(ctx.key(SALT_OP), src, dst)
    op, mom, losses = op0, jax.tree.map(jnp.zeros_like, op0), []
    for b in state["pool"][:tr["first_steps"]]:
        op, mom, loss = step(op, mom, small, b)
        losses.append(float(loss))
    grow = jax.jit(lambda o, t, sq: R.grow(o, t, src, pr, square=sq),
                   static_argnums=2)
    return {"losses": losses, "op0": op0, "op": op,
            "big": grow(op, small, False), "m": grow(op, opt.m, False),
            "v": grow(op, opt.v, True)}


def _by_path(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): x for p, x in flat}


def compare(got: Dict, ref: Dict, log=None) -> Dict[str, float]:
    """The numbers the check compares. ``got`` holds the program's first
    hop (``losses``, ``op``, ``big``, ``m``, ``v``), ``ref`` the
    reference's."""
    lg = [float(x) for x in got["losses"]]
    lr_ = ref["losses"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(lg, lr_)) \
        if len(lg) == len(lr_) else float("inf")
    # the operator's change over the hop, leaf by leaf
    g_op, r_op, r_op0 = (_by_path(got["op"]), _by_path(ref["op"]),
                         _by_path(ref["op0"]))
    paths = sorted(r_op)
    if any(p not in g_op for p in paths):
        return {"loss_gap": loss_gap, "op_change_gap": float("inf"),
                "grown_err": float("inf")}
    norm = lambda x: float(jnp.linalg.norm(x.astype(jnp.float32).ravel()))  # noqa: E731,E501
    want = [norm(r_op[p] - r_op0[p]) for p in paths]
    have = [norm(g_op[p].astype(jnp.float32) - r_op0[p]) for p in paths]
    med = sorted(want)[len(want) // 2]
    keep = [w >= 1e-3 * med for w in want]
    if log is not None and not all(keep):
        log(f"check: operator leaves left out (reference change under "
            f"1e-3 of the median): {[p for p, k in zip(paths, keep) if not k]}")
    op_gap = R.worst_norm_gap(have, want, keep)
    grown = max(R.worst_rel_err(got[k], ref[k]) for k in ("big", "m", "v"))
    return {"loss_gap": loss_gap, "op_change_gap": op_gap,
            "grown_err": grown}


def program_first_hop(state) -> Dict:
    f = state["first"]
    return {"losses": f["losses"], "op": f["op"], "big": f["big"],
            "m": f["opt"].m, "v": f["opt"].v, "count": f["opt"].count}


def readings(ctx, state, control: bool = True
             ) -> Dict[str, Dict[str, float]]:
    """The compared numbers of the program's first hop and, with
    ``control``, of the control (the reference in the program's place, in
    8-bit floats), both against the reference, from one set-up."""
    got = program_first_hop(state)
    got.pop("count")
    state.pop("first")
    ref = reference_hop(ctx, state)
    out = {"program": compare(got, ref, ctx.log)}
    if control:
        out["control"] = compare(reference_hop(ctx, state, R.CONTROL), ref)
    return out


def check(ctx, state):
    got = program_first_hop(state)
    count_ok = int(got.pop("count")) == ctx.traffic["adam_count"]
    for k in ("cfg1", "cfg2"):
        state.pop(k, None)
    state.pop("first")
    ref = reference_hop(ctx, state)
    nums = compare(got, ref, ctx.log)
    if not count_ok:
        nums["grown_err"] = float("inf")
    return ctx.checks(nums)
