"""Driver: whole LiGO hops back to back, sharded over a mesh of chips.

As ``ligo_hop``, for a growth pair whose grown tree does not fit one chip.
The run's chips form the traffic's ``mesh`` over ``axes`` (``data`` x
``model``), and every program of a hop runs under it (``jax.set_mesh``):

- set-up makes the source weights and AdamW state from the seed on the
  mesh, laid out as ``params_pspecs`` prescribes, and a pool of distinct
  causal-LM batches (tokens and next-token targets) laid out as
  ``batch_specs`` does, one per LiGO step;
- each hop is one call of ``repro.core.grow.grow(method="ligo")``: the LiGO
  phase's chunk program over the data-sharded batches, then the grown
  parameters and both moments through the plan's sharded executor, whose
  outputs land sharded (FSDP over ``data``, TP over ``model``), so no chip
  ever holds the grown tree whole. ``hop_s`` is the window over the whole
  hops it completed.

Every hop runs its phase in chunks of ``gcd(first_steps, ligo_steps)``
steps, so the first hop, made in set-up with ``first_steps`` steps,
compiles the one chunk program and every growth program the window's hops
run, and the check compares the program the window runs. The first hop's
losses, trained operator (on the host) and moment count stay for the
check; its grown
parameters and moments (19.6 GB at the cell's size) would not fit on the
chips beside a hop, and moving them to the host took 40 s of set-up, so
the check grows them again, a tree at a time, from that operator through
the same program calls as the hop (``apply_ligo``, ``grow_adamw_state``),
which are deterministic, and compares each with the plain reference's
(``lib/reference_starcoder2``) following the same steps on the same mesh.
Set-up logs the seconds and the compiling of each of its stages.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from benchmarks.chip.lib import harness as H
from benchmarks.chip.lib import reference_starcoder2 as R
from benchmarks.chip.lib import tokens, weights
from benchmarks.chip.lib.programs import program_configs
from benchmarks.chip.lib.reference import worst_norm_gap

SALT_SMALL, SALT_MOMENTS, SALT_OP, SALT_DATA = 1, 2, 3, 4
TRAINING = True         # planted faults bound the limits (tools/limits.py)
FAULTS_AS = "ligo_hop"  # ligo_hop's faults patch the same repro.core.grow
TREES = ("big", "m", "v")


def clm_batch(seed: int, step: int, batch: int, seq: int, vocab: int
              ) -> Dict[str, np.ndarray]:
    """A causal-LM batch: ``seq`` tokens of each row and the token after
    each, from the seeded stream."""
    toks = tokens.gen_tokens(seed, step, batch, seq, vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def source_params(key, m: Dict, dtype) -> Dict:
    """The source model's weights in the program's layout for a StarCoder2
    block (traced; call under ``jax.jit``): grouped-query projections of
    ``n_heads`` and ``n_kv_heads`` heads of ``d_head``, biases, LayerNorms,
    a tied head and rotary positions (no position table). Matrices and
    biases truncated normal with std 0.02 (``lib/weights``), norm scales
    1 + that."""
    L, d, ff, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    ks = iter(jax.random.split(key, 32))
    normal = lambda *shape: (weights.STD * jax.random.truncated_normal(  # noqa: E731,E501
        next(ks), -2.0, 2.0, shape)).astype(dtype)

    def norm(*lead):
        return {"scale": (1.0 + normal(*lead, d).astype(jnp.float32)
                          ).astype(dtype), "bias": normal(*lead, d)}

    layers = {"ln1": norm(L), "ln2": norm(L),
              "wq": normal(L, d, q), "bq": normal(L, q),
              "wk": normal(L, d, kv), "bk": normal(L, kv),
              "wv": normal(L, d, kv), "bv": normal(L, kv),
              "wo": normal(L, q, d), "bo": normal(L, d),
              "mlp": {"w1": normal(L, d, ff), "b1": normal(L, ff),
                      "w2": normal(L, ff, d), "b2": normal(L, d)}}
    return {"embed": {"tok": normal(V, d)}, "layers": {"attn": layers},
            "final_norm": norm()}


def make_inputs(ctx, mesh) -> Dict:
    """Source weights, AdamW state and the batch pool on ``mesh``, from the
    seed."""
    from repro.distributed.sharding import (batch_specs, named_shardings,
                                            params_pspecs)
    from repro.optim import AdamWState
    cfg, tr = ctx.config, ctx.traffic
    src, dtype = cfg["src"], weights.DTYPES[cfg["dtype"]]
    make = lambda k: source_params(k, src, dtype)                 # noqa: E731
    sizes = dict(mesh.shape)
    specs = params_pspecs(jax.eval_shape(make, ctx.key(SALT_SMALL)),
                          model_size=sizes["model"], dp_size=sizes["data"])
    sh = named_shardings(specs, mesh)
    small = jax.jit(make, out_shardings=sh)(ctx.key(SALT_SMALL))
    m, v = jax.jit(weights.make_moments, out_shardings=(sh, sh))(
        ctx.key(SALT_MOMENTS), small)
    opt = AdamWState(m=m, v=v, count=jnp.asarray(tr["adam_count"],
                                                 jnp.int32))
    seed = ctx.np_seed(SALT_DATA)
    batches = [clm_batch(seed, k, tr["batch"], tr["seq"], src["vocab_size"])
               for k in range(tr["ligo_steps"])]
    bsh = named_shardings(batch_specs(batches[0], dp_size=sizes["data"]),
                          mesh)
    pool = [jax.device_put(b, bsh) for b in batches]
    return {"small": small, "opt": opt, "pool": pool}


def make_mesh(ctx):
    from repro.launch.mesh import make_mesh as mesh_of
    tr = ctx.traffic
    return mesh_of(tuple(tr["mesh"]), tuple(tr["axes"]))


def scan_chunk(traffic) -> int:
    """The LiGO phase's chunk: one that divides the first hop's steps and a
    window hop's, so that both run one chunk program."""
    return math.gcd(traffic["first_steps"], traffic["ligo_steps"])


def hop(ctx, state, steps: int):
    """One whole hop through the program's ``grow`` on the mesh; waits for
    every output."""
    from repro.core.grow import grow
    tr = ctx.traffic
    with jax.set_mesh(state["mesh"]):
        big, info = grow(state["small"], state["cfg1"], state["cfg2"],
                         method="ligo", key=ctx.key(SALT_OP),
                         data_it=iter(state["pool"][:steps]),
                         ligo_steps=steps, ligo_lr=tr["ligo_lr"],
                         ligo_momentum=tr["ligo_momentum"],
                         opt_state=state["opt"], loss_chunk=tr["loss_chunk"],
                         ligo_scan_chunk=scan_chunk(tr))
    jax.block_until_ready((big, info["opt_state"], info["operator"]))
    return big, info


def _route_counts() -> Dict[str, int]:
    """The program's counts of leaf groups by route over its executor
    builds so far (0 where it keeps none)."""
    from repro import obs
    out = {}
    for k in ("fused", "einsum"):
        c = obs.REGISTRY.get(f"ligo.groups_{k}")
        out[k] = c.value if c is not None else 0
    return out


class _Stages:
    """Logs each stage of set-up: its seconds and its compiling."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.t, self.c = time.perf_counter(), self._compiled()

    def _compiled(self) -> Dict[str, float]:
        clock = self.ctx.clock
        return clock.snapshot() if clock else {"compiles": 0,
                                                "compile_s": 0.0}

    def done(self, name: str) -> None:
        t, c = time.perf_counter(), self._compiled()
        self.ctx.log(f"set-up stage {name}: {t - self.t:.3f} s, "
                     f"{c['compiles'] - self.c['compiles']} compiles "
                     f"{c['compile_s'] - self.c['compile_s']:.3f} s")
        self.t, self.c = t, c


def setup(ctx):
    try:
        cfg1, cfg2 = program_configs(ctx.config, ctx.log)
    except KeyError as e:
        raise H.BenchError(f"the program cannot build this pair: {e}")
    stages = _Stages(ctx)
    mesh = make_mesh(ctx)
    state = make_inputs(ctx, mesh)
    state.update(cfg1=cfg1, cfg2=cfg2, mesh=mesh)
    jax.block_until_ready((state["small"], state["opt"], state["pool"]))
    ctx.mark("init")
    stages.done("init")
    from repro.core.plan import plan_for
    plan = plan_for(cfg1, cfg2, state["small"])
    fused = plan.fused_routes(jax.default_backend() == "tpu", mesh)
    ctx.records["kernel_groups"] = kernel_groups(plan, mesh, fused)
    ctx.log(f"plan on mesh {dict(mesh.shape)}: {sum(fused)}/{len(fused)} "
            f"groups on the fused kernels at one shard's shape")
    with ctx.span("bench.grow"):
        big, info = hop(ctx, state, ctx.traffic["first_steps"])
    stages.done(f"first hop ({ctx.traffic['first_steps']} steps in chunks "
                f"of {scan_chunk(ctx.traffic)})")
    # the first hop has built every executor and the one chunk program
    # the window's hops run
    ctx.records["ligo_groups"] = dict(_route_counts(),
                                      groups=len(plan.groups))
    # the operator waits on the host (1.1 GB at the cell's size), for the
    # room the check's reference needs on the chips
    op = info["operator"]
    state["first"] = {"losses": [float(x) for x in info["ligo_losses"]],
                      "op": jax.device_get(op),
                      "op_sharding": jax.tree.map(lambda x: x.sharding, op),
                      "count": int(info["opt_state"].count)}
    del big, info, op
    return state


def kernel_groups(plan, mesh, fused) -> list:
    """Shapes of the groups on the fused route as one shard's launch sees
    them: w (G, L2, L1), B (I, A), W (G, L1, E, A, Bd), bf16 W and P. The
    trace holds every chip's launches, so each reads its own shard."""
    out = []
    for g, f in zip(plan.groups, fused):
        if not f:
            continue
        G, Bd = plan.shard_shape(g, mesh)
        L2, I, itemsize = g.fuse_dims
        out.append({"G": G, "L1": g.shape[0], "L2": L2,
                    "E": g.shape[1] if len(g.shape) == 4 else 1,
                    "I": I, "A": g.shape[-2], "Bd": Bd,
                    "itemsize": itemsize, "paths": list(g.paths)})
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
    ctx.log(f"window: {hops} hops in {window_s:.3f} s")
    return {"window_s": window_s, "hops": hops, "hop_s": window_s / hops,
            "attempted": hops, "failed": 0}


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def reference_phase(ctx, state, pr=R.REF) -> Dict:
    """The plain reference following the first hop's steps from the same
    weights and batches on the same mesh: its losses, and the operator
    before and after."""
    tr, mesh = ctx.traffic, state["mesh"]
    src, dst = ctx.config["src"], ctx.config["dst"]
    rep = NamedSharding(mesh, PartitionSpec())

    # the source is an argument: closed over, it would be folded into the
    # program as constants
    @jax.jit
    def step(op, mom, small, batch):
        loss, g = jax.value_and_grad(R.ligo_loss)(op, small, src, dst,
                                                  batch, pr)
        op, mom = R.sgd_momentum(op, mom, g, lr=tr["ligo_lr"],
                                 momentum=tr["ligo_momentum"])
        return op, mom, loss

    with jax.set_mesh(mesh):
        op0 = jax.device_put(R.init_operator(ctx.key(SALT_OP), src, dst),
                             rep)
        op, mom, losses = op0, jax.tree.map(jnp.zeros_like, op0), []
        for b in state["pool"][:tr["first_steps"]]:
            op, mom, loss = step(op, mom, state["small"], b)
            losses.append(float(loss))
    return {"losses": losses, "op0": jax.device_get(op0), "op": op}


def reference_tree(ctx, state, op, which: str, pr=R.REF):
    """The reference's grown parameters (``big``) or moment (``m``, ``v``)
    through the operator ``op``, float32, on the mesh."""
    src, dst = ctx.config["src"], ctx.config["dst"]
    # the parameters cast to float32 grow through the program compiled for
    # the first moment
    source = {"big": jax.tree.map(lambda x: x.astype(jnp.float32),
                                  state["small"]),
              "m": state["opt"].m, "v": state["opt"].v}[which]
    square = which == "v"
    progs = state.setdefault("reference_grow", {})
    if (pr, square) not in progs:
        progs[pr, square] = jax.jit(
            lambda o, t: R.grow(o, t, src, dst, pr, square=square))
    with jax.set_mesh(state["mesh"]):
        return progs[pr, square](op, source)


def program_tree(state, got: Dict, which: str):
    """The program's grown parameters (``big``) or moment (``m``, ``v``)
    through the first hop's trained operator, laid out as the hop left it,
    by the calls ``grow`` makes after its phase, on the mesh."""
    from repro.core.ligo import apply_ligo
    from repro.optim import grow_adamw_state
    cfg1, cfg2 = state["cfg1"], state["cfg2"]
    op = jax.device_put(got["op"], got["op_sharding"])
    with jax.set_mesh(state["mesh"]):
        if which == "big":
            return apply_ligo(op, state["small"], cfg1, cfg2)
        return getattr(grow_adamw_state(state["opt"], op, cfg1, cfg2), which)


@jax.jit
def _leaf_norms(got, want):
    """Per leaf: ||got - want|| and ||want||, float32."""
    def two(g, w):
        d = g.astype(jnp.float32) - w
        return jnp.sqrt(jnp.sum(d * d)), jnp.sqrt(jnp.sum(w * w))
    return jax.tree.map(two, got, want)


def _by_path(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in flat}


def compare(ctx, state, got: Dict, ref: Dict,
            got_tree: Callable[[str], Dict]) -> Dict[str, float]:
    """The numbers the check compares. ``got`` holds the losses and trained
    operator of a first hop and ``got_tree`` gives its grown trees by name;
    ``ref`` holds the reference's losses and operators, and the reference's
    trees are grown here one at a time, so that two of them are never on
    the chips together."""
    lg, lr_ = [float(x) for x in got["losses"]], ref["losses"]
    loss_gap = (max(abs(a - b) / abs(b) for a, b in zip(lg, lr_))
                if len(lg) == len(lr_) else math.inf)
    g_op, r_op, r_op0 = (_by_path(got["op"]), _by_path(ref["op"]),
                         _by_path(ref["op0"]))
    paths = sorted(r_op)
    if any(p not in g_op for p in paths):
        return {"loss_gap": loss_gap, "op_change_gap": math.inf,
                "grown_err": math.inf}
    norm = lambda x: float(np.linalg.norm(x.ravel()))             # noqa: E731
    want = [norm(r_op[p] - r_op0[p]) for p in paths]
    have = [norm(g_op[p] - r_op0[p]) for p in paths]
    med = sorted(want)[len(want) // 2]
    keep = [w >= 1e-3 * med for w in want]
    if not all(keep):
        ctx.log(f"check: operator leaves left out (reference change under "
                f"1e-3 of the median): "
                f"{[p for p, k in zip(paths, keep) if not k]}")
    op_gap = worst_norm_gap(have, want, keep)
    grown = 0.0
    for which in TREES:
        want_t = reference_tree(ctx, state, ref["op"], which)
        have_t = jax.device_put(got_tree(which),
                                jax.tree.map(lambda w: w.sharding, want_t))
        for d, n in jax.tree.leaves(_leaf_norms(have_t, want_t),
                                    is_leaf=lambda x: isinstance(x, tuple)):
            grown = max(grown, float(d) / max(float(n), 1e-30))
        del want_t, have_t
    return {"loss_gap": loss_gap, "op_change_gap": op_gap,
            "grown_err": grown}


def readings(ctx, state, control: bool = True
             ) -> Dict[str, Dict[str, float]]:
    """The compared numbers of the program's first hop and, with
    ``control``, of the control (the reference in the program's place, in
    8-bit floats), both against the reference, from one set-up."""
    got = state.pop("first")
    ref = reference_phase(ctx, state)
    out = {"program": compare(
        ctx, state, got, ref, lambda w: program_tree(state, got, w))}
    if control:
        ctl = reference_phase(ctx, state, R.CONTROL)
        out["control"] = compare(
            ctx, state, ctl, ref,
            lambda w: reference_tree(ctx, state, ctl["op"], w, R.CONTROL))
    return out


def check(ctx, state):
    got = state.pop("first")
    count_ok = int(got["count"]) == ctx.traffic["adam_count"]
    nums = compare(ctx, state, got, reference_phase(ctx, state),
                   lambda w: program_tree(state, got, w))
    if not count_ok:
        nums["grown_err"] = math.inf
    return ctx.checks(nums)
