"""StarCoder2 growth against the plain reference, at a small StarCoder2-shaped
pair: grouped-query heads whose group size changes (3 query heads a kv
head -> 2) and whose kv heads grow (2 -> 4), sequences longer than the
sliding window, LayerNorm eps 1e-5, a tied head, seeded random weights.

The program runs its normal path (``grow(method="ligo")`` with the AdamW
state, the chunked causal-LM loss, the grown layers recomputed as at
StarCoder2's sizes on the chip) on one device, and
on a 2x2 (data, model) mesh in a subprocess; both are compared with
``benchmarks/chip/lib/reference_starcoder2`` (float32, ``highest``):
logits, the LiGO loss, the losses and operator of a few LiGO steps, and
the grown parameters and both moments. The same comparison fails when the
reference computes in bf16. The hop's spans name the mesh, and the route
counters add up to the plan's groups.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, run_in_subprocess

sys.path.insert(0, REPO)

from benchmarks.chip.lib import reference_starcoder2 as RS  # noqa: E402
from repro.configs import get_config  # noqa: E402

T, BATCH, STEPS, LR, MOM = 40, 2, 3, 1e-3, 0.9
SRC = dict(n_layers=2, d_model=96, n_heads=6, n_kv_heads=2, d_head=16,
           d_ff=192, vocab_size=256, max_seq=64, window=16)
DST = dict(n_layers=3, d_model=128, n_heads=8, n_kv_heads=4, d_head=16,
           d_ff=256, vocab_size=256, max_seq=64, window=16)
# Tolerances. The program and the reference both compute in float32 here
# (on the CPU a float32 product is exact to float32 rounding), so they
# differ by rounding in different orders of summation: about 1e-6 of a
# value's scale through a few layers of width <= 256, and somewhat more
# where a gradient subtracts nearly equal terms. Each limit leaves 10-100x
# room above that, and lies well under what bf16 operands give (about
# 4e-3 relative per product).
TOL = {"logit_err": 1e-4,      # max |dlogit| over max |logit|
       "loss_gap": 1e-5,       # relative gap of each LiGO step's loss
       "op_gap": 1e-3,         # operator change after the steps, per leaf
       "grown_err": 1e-5}      # grown params and moments, per leaf


def configs():
    c1 = get_config("starcoder2-3b").scaled(dtype="float32", **SRC)
    c2 = get_config("starcoder2-7b").scaled(dtype="float32", **DST)
    return c1, c2


def model_dict(cfg):
    """A config as the reference reads it."""
    return {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
        "norm_eps", "rope_theta", "window")}


def inputs(c1, key=0):
    """Seeded random weights (biases and norms off their init), AdamW
    moments of a run under way, and STEPS causal-LM batches."""
    from repro.models import init_params
    from repro.optim import AdamWState
    k = jax.random.split(jax.random.PRNGKey(key), 4)
    small = init_params(c1, k[0])
    leaves, tdef = jax.tree.flatten(small)
    ks = jax.random.split(k[1], len(leaves))
    small = tdef.unflatten([x + 0.05 * jax.random.normal(kk, x.shape)
                            for kk, x in zip(ks, leaves)])
    m = tdef.unflatten([1e-3 * jax.random.normal(kk, x.shape)
                        for kk, x in zip(jax.random.split(k[2], len(leaves)),
                                         leaves)])
    opt = AdamWState(m=m, v=jax.tree.map(lambda x: x * x + 1e-7, m),
                     count=jnp.asarray(7, jnp.int32))
    toks = jax.random.randint(k[3], (STEPS, BATCH, T + 1), 0, c1.vocab_size)
    batches = [{"tokens": t[:, :-1], "targets": t[:, 1:]} for t in toks]
    return small, opt, batches


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm((a - b).ravel())
                 / max(np.linalg.norm(b.ravel()), 1e-30))


def _worst(got, want):
    return max(_rel(a, b) for a, b in zip(jax.tree.leaves(got),
                                          jax.tree.leaves(want)))


def numbers(mesh_shape=None, pr_name="f32"):
    """The compared numbers of the program's normal path against the
    reference computed at ``pr_name`` (f32 or bf16), on one device or on a
    mesh of ``mesh_shape`` (data, model); and what the hop's spans and
    route counters say."""
    import importlib
    # the module, not the function ``repro.core`` exports by its name
    G = importlib.import_module("repro.core.grow")
    pr = {"f32": RS.REF, "bf16": RS.BF16}[pr_name]
    # the LiGO phase recomputes the grown layers, as it does on the chip at
    # StarCoder2's sizes (the CPU reports no memory, so it never would)
    needed, G.ligo_remat_needed = G.ligo_remat_needed, lambda *a, **k: True
    try:
        return _numbers(mesh_shape, pr)
    finally:
        G.ligo_remat_needed = needed


def _numbers(mesh_shape, pr):
    import contextlib
    from repro import obs
    from repro.core.grow import grow, ligo_loss
    from repro.core.ligo import init_ligo_params
    from repro.core.plan import GROUPS_EINSUM, GROUPS_FUSED, plan_for
    from repro.models import forward, unembed
    c1, c2 = configs()
    src, dst = model_dict(c1), model_dict(c2)
    small, opt, batches = inputs(c1)
    key = jax.random.PRNGKey(11)
    mesh, ctx = None, contextlib.nullcontext()
    if mesh_shape:
        from repro.distributed.sharding import (batch_specs, named_shardings,
                                                params_pspecs)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(mesh_shape, ("data", "model"))
        sh = named_shardings(params_pspecs(small, model_size=mesh_shape[1],
                                           dp_size=mesh_shape[0]), mesh)
        small = jax.device_put(small, sh)
        opt = opt._replace(m=jax.device_put(opt.m, sh),
                           v=jax.device_put(opt.v, sh))
        bsh = named_shardings(batch_specs(batches[0]), mesh)
        batches = [jax.device_put(b, bsh) for b in batches]
        ctx = jax.set_mesh(mesh)
    out = {}
    with ctx:
        # logits of the grown architecture on the source-shaped weights'
        # growth, and the LiGO loss at the operator's start
        op0 = init_ligo_params(key, c1, c2)
        big0 = jax.jit(lambda o, s: RS.grow(o, s, src, dst))(op0, small)
        lg = unembed(big0, c2, forward(big0, c2, batches[0])[0])
        want = jax.jit(lambda p, t: RS.logits(p, dst, t, pr))(
            big0, batches[0]["tokens"])
        out["logit_err"] = float(jnp.max(jnp.abs(lg - want))
                                 / jnp.max(jnp.abs(want)))
        got0 = float(jax.jit(lambda o, s, b: ligo_loss(
            o, s, c1, c2, b, loss_chunk=8))(op0, small, batches[0]))
        ref0 = float(jax.jit(lambda o, s, b: RS.ligo_loss(
            o, s, src, dst, b, pr))(op0, small, batches[0]))
        out["loss0_gap"] = abs(got0 - ref0) / abs(ref0)

        # the normal path: a whole hop with the AdamW state
        obs.FLIGHT.clear()
        big, info = grow(small, c1, c2, method="ligo", key=key,
                         data_it=iter(batches), ligo_steps=STEPS, ligo_lr=LR,
                         ligo_momentum=MOM, opt_state=opt, loss_chunk=8)
        spans = obs.FLIGHT.events(type="span")
        out["spans"] = {s["name"]: [s["attrs"].get("mesh"),
                                    s["attrs"].get("devices")]
                        for s in spans if s["name"] in ("grow", "ligo.chunk")}

        @jax.jit
        def step(op, mom, b):
            loss, g = jax.value_and_grad(RS.ligo_loss)(op, small, src, dst,
                                                       b, pr)
            op, mom = RS.sgd_momentum(op, mom, g, lr=LR, momentum=MOM)
            return op, mom, loss

        op = RS.init_operator(key, src, dst)
        mom, losses = jax.tree.map(jnp.zeros_like, op), []
        for b in batches:
            op, mom, loss = step(op, mom, b)
            losses.append(float(loss))
        out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in
                              zip(info["ligo_losses"], losses))
        # per leaf: how far the program's operator lies from the
        # reference's, against the reference's change over the steps
        # (leaves that barely move, as the key bias's blend, left out)
        flat = lambda t: {jax.tree_util.keystr(p): np.asarray(x)   # noqa
                          for p, x in jax.tree_util.tree_flatten_with_path(
                              t)[0]}
        g_op, r_op, r_op0 = flat(info["operator"]), flat(op), flat(op0)
        change = {p: np.linalg.norm((r_op[p] - r_op0[p]).ravel())
                  for p in r_op}
        med = float(np.median(list(change.values())))
        out["op_gap"] = max(float(np.linalg.norm((g_op[p] - r_op[p]).ravel())
                                  / change[p])
                            for p in r_op if change[p] >= 1e-3 * med)
        grown = jax.jit(lambda o, t, sq: RS.grow(o, t, src, dst, pr,
                                                 square=sq),
                        static_argnums=2)
        gopt = info["opt_state"]
        out["grown_err"] = max(_worst(big, grown(op, small, False)),
                               _worst(gopt.m, grown(op, opt.m, False)),
                               _worst(gopt.v, grown(op, opt.v, True)))
        out["count"] = int(gopt.count)
        out["sharded"] = [str(big["layers"]["attn"]["wq"].sharding.spec),
                          str(gopt.v["embed"]["tok"].sharding.spec)] \
            if mesh is not None else []

        # one executor build counts every group once, on one route
        plan = plan_for(c1, c2, small)
        plan._executors.clear()
        before = (GROUPS_FUSED.value, GROUPS_EINSUM.value)
        plan.executor(mesh=mesh, square=True)
        out["routes"] = [GROUPS_FUSED.value - before[0],
                         GROUPS_EINSUM.value - before[1], len(plan.groups)]
        # on the kernel route every fused group is one launch (per shard
        # under shard_map), counted where it is traced
        from repro.kernels.ops import LAUNCH_COUNTS
        plan._executors.clear()
        before = (GROUPS_FUSED.value, LAUNCH_COUNTS.get("fwd"))
        plan.executor(use_kernel=True, mesh=mesh).lower(op0, small)
        out["kernel_routes"] = [GROUPS_FUSED.value - before[0],
                                LAUNCH_COUNTS.get("fwd") - before[1]]
    return out


def _check(got):
    for k, lim in TOL.items():
        assert got[k] <= lim, (k, got[k], lim)
    assert got["loss0_gap"] <= TOL["loss_gap"], got["loss0_gap"]
    assert got["count"] == 7


@pytest.fixture(scope="module")
def one_device():
    return numbers()


def test_one_device_matches_reference(one_device):
    _check(one_device)


def test_one_device_spans_and_routes(one_device):
    assert one_device["spans"] == {"grow": ["none", 1],
                                   "ligo.chunk": ["none", 1]}
    fused, einsum, groups = one_device["routes"]
    assert fused + einsum == groups and fused == 0      # CPU: einsum route


def test_bf16_reference_fails_the_comparison():
    """The reference with bf16 operands in the program's place of float32
    falls outside the tolerances: they are tight enough to see it."""
    got = numbers(pr_name="bf16")
    assert [k for k, lim in TOL.items() if got[k] > lim]


@pytest.fixture(scope="module")
def mesh_2x2():
    code = (f"import json, sys; sys.path.insert(0, {os.path.dirname(__file__)!r})\n"
            "import test_starcoder2_ligo as T\n"
            "print('RESULT ' + json.dumps(T.numbers((2, 2))))\n")
    out = run_in_subprocess(code, n_devices=4, timeout=900)
    line = [x for x in out.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_2x2_matches_reference(mesh_2x2):
    _check(mesh_2x2)
    # grown leaves land FSDP + TP sharded, as params_pspecs prescribes
    assert mesh_2x2["sharded"] == ["PartitionSpec(None, 'data', 'model')",
                                   "PartitionSpec('model', 'data')"]


def test_mesh_2x2_spans_and_routes(mesh_2x2):
    assert mesh_2x2["spans"] == {"grow": ["2x2", 4],
                                 "ligo.chunk": ["2x2", 4]}
    fused, einsum, groups = mesh_2x2["routes"]
    assert fused + einsum == groups
    fused, launches = mesh_2x2["kernel_routes"]
    assert fused > 0 and launches == fused


def test_ligo_phase_recomputes_only_what_does_not_fit():
    """The LiGO phase keeps BERT-Base's activations at the one-chip cell's
    8 x 512 on a 16 GiB chip, recomputes the StarCoder2-7B stage's at
    2 x 4096 over a 2x2 mesh, and never recomputes where the backend
    reports no memory (the CPU)."""
    from repro.core.grow import ligo_remat_needed, saved_activation_bytes
    gib = 2 ** 30
    bert = get_config("bert-base")
    assert saved_activation_bytes(bert, 8, 512, {}) < 8 * gib
    assert not ligo_remat_needed(bert, (8, 512), bytes_limit=16 * gib)
    assert ligo_remat_needed(bert, (32, 512), bytes_limit=16 * gib)
    stage = get_config("starcoder2-7b").scaled(n_layers=8)
    kept = saved_activation_bytes(stage, 2, 4096, {"data": 2, "model": 2})
    assert kept > 16 * gib
    # the mesh splits the batch and the heads: one device would keep more
    assert saved_activation_bytes(stage, 2, 4096, {}) > 3 * kept
    assert not ligo_remat_needed(stage, (2, 4096))
