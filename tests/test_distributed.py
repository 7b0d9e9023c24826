"""Distribution tests (each runs in a subprocess with 8 host devices):
pjit-sharded training == single-device training, sequence-parallel residual
stream preserves numerics, pipeline parallelism == sequential stages,
compressed cross-pod psum, sharded global batch loading, and the sharded
GrowthPlan end-to-end (ambient-mesh pickup + sharded LiGO phase)."""
import pytest


def test_sharded_growth_end_to_end(subproc):
    """The full distributed-growth path on an 8-device 2x4 mesh: apply_ligo
    picks the ambient mesh up automatically, the sharded executor matches
    the legacy walk, grown leaves land partitioned, and the LiGO training
    phase (jitted scan differentiating through the sharded plan) runs."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.paper_models import BERT_SMALL
from repro.core import apply_ligo, init_ligo_params, plan_for, train_ligo
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.inputs import dummy_batch

c1 = BERT_SMALL.scaled(name="sg1", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=4, d_head=8, d_ff=64, vocab_size=64,
                       max_seq=64, dtype="float32")
c2 = c1.scaled(name="sg2", n_layers=4, d_model=64, n_heads=8, n_kv_heads=8,
               d_ff=128)
sp = init_params(c1, jax.random.PRNGKey(0))
lg = init_ligo_params(jax.random.PRNGKey(1), c1, c2)
mesh = make_mesh((2, 4), ("data", "model"))
legacy = apply_ligo(lg, sp, c1, c2, engine="legacy")
with jax.set_mesh(mesh):
    big = apply_ligo(lg, sp, c1, c2)          # ambient mesh -> sharded plan
for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(big)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
assert any(not l.sharding.is_fully_replicated for l in jax.tree.leaves(big))

def batches():
    while True:
        yield dummy_batch(c1, 2, 16, "train")
with jax.set_mesh(mesh):
    _, losses = train_ligo(lg, sp, c1, c2, batches(), steps=4, scan_chunk=2)
assert len(losses) == 4 and all(np.isfinite(losses)), losses
print("SHARDED_GROW_OK")
"""
    assert "SHARDED_GROW_OK" in subproc(code)


def test_pjit_train_step_matches_unsharded(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.paper_models import GPT2_BASE
from repro.configs.base import TrainConfig
from repro.data import batch_for_step
from repro.training import init_train_state, make_train_step
from repro.distributed.sharding import params_pspecs, named_shardings, batch_specs

cfg = GPT2_BASE.scaled(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                       d_head=16, d_ff=128, vocab_size=64, max_seq=64, dtype="float32")
tcfg = TrainConfig(steps=10, warmup_steps=2, lr=1e-3)
params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
batch = {k: jnp.asarray(v) for k, v in batch_for_step(cfg, 0, 8, 32, seed=0).items()}

# single device
step1 = jax.jit(make_train_step(cfg, tcfg))
p1, o1, m1 = step1(params, opt, batch, jnp.asarray(0))

# 2x4 mesh pjit
mesh = make_mesh((2, 4), ("data", "model"))
pspecs = params_pspecs(params, model_size=4, dp_size=2)
psh = named_shardings(pspecs, mesh)
osh = type(opt)(m=psh, v=psh, count=NamedSharding(mesh, P()))
bsh = named_shardings(batch_specs(batch, dp_size=2), mesh)
with jax.set_mesh(mesh):
    step2 = jax.jit(make_train_step(cfg, tcfg),
                    in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())))
    p2, o2, m2 = step2(params, opt, batch, jnp.asarray(0))
np.testing.assert_allclose(float(m1["total"]), float(m2["total"]), rtol=1e-4)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)
print("PJIT_OK")
"""
    assert "PJIT_OK" in subproc(code)


def test_sequence_parallel_residual_matches(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.paper_models import GPT2_BASE
from repro.data import batch_for_step
from repro.models import init_params, loss_fn
cfg = GPT2_BASE.scaled(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                       d_head=16, d_ff=128, vocab_size=64, max_seq=64, dtype="float32")
params = init_params(cfg, jax.random.PRNGKey(0))
batch = {k: jnp.asarray(v) for k, v in batch_for_step(cfg, 0, 4, 32, seed=0).items()}
l_plain, _ = loss_fn(params, cfg, batch)
mesh = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    l_sp = jax.jit(lambda p, b: loss_fn(p, cfg, b,
                   act_spec=P("data", "model", None))[0])(params, batch)
np.testing.assert_allclose(float(l_plain), float(l_sp), rtol=1e-5)
print("SP_OK")
"""
    assert "SP_OK" in subproc(code)


def test_pipeline_parallel_equals_sequential(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import pipeline_apply, bubble_fraction
mesh = make_mesh((4,), ("pod",))
S, M, B, D = 4, 8, 16, 32
rng = np.random.RandomState(0)
stage_params = {"w": jnp.asarray(rng.randn(S, D, D) * 0.2, jnp.float32),
                "b": jnp.asarray(rng.randn(S, D) * 0.1, jnp.float32)}
x = jnp.asarray(rng.randn(B, D), jnp.float32)

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

ref = x
for s in range(S):
    ref = stage_fn(jax.tree.map(lambda a: a[s], stage_params), ref)

with jax.set_mesh(mesh):
    out = pipeline_apply(stage_fn, stage_params, x, mesh=mesh, axis="pod",
                         microbatches=M)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
print("PIPE_OK")
"""
    assert "PIPE_OK" in subproc(code)


def test_compressed_psum_shard_map(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum
mesh = make_mesh((4,), ("pod",))
rng = np.random.RandomState(0)
g = jnp.asarray(rng.randn(4, 64), jnp.float32)     # per-pod gradients
err0 = jnp.zeros((4, 64), jnp.float32)

def f(gi, ei):
    out, new_e = compressed_psum(gi[0], "pod", ei[0])
    return out[None], new_e[None]

fn = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P("pod"), P("pod")))
with jax.set_mesh(mesh):
    out, err = fn(g, err0)
mean_ref = np.asarray(g).mean(0)
for i in range(4):
    np.testing.assert_allclose(np.asarray(out[i]), mean_ref, atol=0.05)
# error feedback accumulates the residual
assert float(jnp.abs(err).max()) > 0
print("PSUM_OK")
"""
    assert "PSUM_OK" in subproc(code)


def test_global_batch_loader_sharded(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs.paper_models import GPT2_BASE
from repro.data import GlobalBatchLoader, batch_for_step
from repro.data.pipeline import Prefetcher
cfg = GPT2_BASE.scaled(vocab_size=64)
mesh = make_mesh((4, 2), ("data", "model"))
loader = GlobalBatchLoader(cfg, mesh, batch=8, seq=16, seed=0)
b = loader.batch_at(0)
host = batch_for_step(cfg, 0, 8, 16, seed=0)
for k in host:
    np.testing.assert_array_equal(np.asarray(b[k]), host[k])
    assert b[k].sharding.spec[0] == ("data",) or b[k].sharding.spec[0] == "data"
pf = Prefetcher(iter(loader), prefetch=2)
nxt = next(pf)
np.testing.assert_array_equal(np.asarray(nxt["tokens"]), host["tokens"])
pf.close()
print("LOADER_OK")
"""
    assert "LOADER_OK" in subproc(code)


def test_dryrun_machinery_small_mesh(subproc):
    """The dry-run builder end-to-end on a small mesh (fast smoke of (e))."""
    code = """
import jax, jax.numpy as jnp
from repro.configs import smoke_config, ASSIGNED, SHAPES
from repro.launch.dryrun import build_cell
from repro.launch.mesh import make_mesh
from repro.roofline.hlo import collect_hlo_stats
import dataclasses
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config(ASSIGNED["llama3-8b"])
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
fn, args, in_sh, out_sh, meta = build_cell(cfg, shape, mesh)
with jax.set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
stats = collect_hlo_stats(compiled.as_text())
assert stats["dot_flops"] > 0
assert compiled.memory_analysis().temp_size_in_bytes > 0
print("DRYRUN_OK", int(stats["dot_flops"]))
"""
    assert "DRYRUN_OK" in subproc(code)


def test_shardmap_moe_matches_dense(subproc):
    """Explicit-collective MoE == dense dispatch (both rep paths) + grads."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import ASSIGNED, smoke_config
from repro.models.moe import apply_moe, init_moe
from repro.models.moe_shardmap import apply_moe_shardmap, moe_shardmap_available
rng = np.random.RandomState(0)
# rep=1 (E=4 experts on data=2)
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config(ASSIGNED["qwen3-moe-30b-a3b"]).scaled(capacity_factor=8.0)
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jnp.asarray(rng.randn(4, 8, cfg.d_model), jnp.float32) * 0.3
ref, _ = apply_moe(p, x, cfg)
with jax.set_mesh(mesh):
    assert moe_shardmap_available(cfg)
    out, _ = jax.jit(lambda pp, xx: apply_moe_shardmap(pp, xx, cfg))(p, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
# rep=2 virtual replication (E=2 on data=4)
cfg2 = smoke_config(ASSIGNED["mixtral-8x7b"]).scaled(
    n_experts=2, experts_top_k=1, capacity_factor=8.0)
mesh2 = make_mesh((4, 2), ("data", "model"))
p2 = init_moe(jax.random.PRNGKey(1), cfg2)
x2 = jnp.asarray(rng.randn(4, 8, cfg2.d_model), jnp.float32) * 0.3
ref2, _ = apply_moe(p2, x2, cfg2)
with jax.set_mesh(mesh2):
    out2, _ = jax.jit(lambda pp, xx: apply_moe_shardmap(pp, xx, cfg2))(p2, x2)
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=1e-5)
# differentiable
with jax.set_mesh(mesh2):
    g = jax.grad(lambda pp: jnp.sum(apply_moe_shardmap(pp, x2, cfg2)[0] ** 2))(p2)
assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
print("SHARDMAP_MOE_OK")
"""
    assert "SHARDMAP_MOE_OK" in subproc(code)


def test_moe_block_dispatches_shardmap(subproc):
    """cfg.moe_impl='shard_map' routes through the explicit-collective path
    inside the full model forward (same loss as dense)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import ASSIGNED, smoke_config
from repro.models import init_params, loss_fn
from repro.models.inputs import dummy_batch
mesh = make_mesh((2, 4), ("data", "model"))
cfg = smoke_config(ASSIGNED["qwen3-moe-30b-a3b"])
params = init_params(cfg, jax.random.PRNGKey(0))
batch = dummy_batch(cfg, 2, 16, "train")
_, m_dense = loss_fn(params, cfg, batch)
cfg_sm = cfg.scaled(moe_impl="shard_map")
with jax.set_mesh(mesh):
    _, m_sm = jax.jit(lambda p, b: loss_fn(p, cfg_sm, b))(params, batch)
# CE must match exactly; the aux load-balance loss uses per-shard fractions
# (standard local-dispatch semantics) and may differ slightly.
np.testing.assert_allclose(float(m_dense["loss"]), float(m_sm["loss"]),
                           rtol=1e-6)
print("MOE_DISPATCH_OK")
"""
    assert "MOE_DISPATCH_OK" in subproc(code)
