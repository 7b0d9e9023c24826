"""Elastic fault tolerance: train on N devices, crash, resume on N/2.

Runs itself twice via subprocess with different forced device counts to
demonstrate that a checkpoint written under one mesh restores (and keeps the
loss trajectory) under another — the shrunk-fleet recovery path.

    PYTHONPATH=src python examples/elastic_restart.py
"""
import os
import subprocess
import sys

PHASE_CODE = r"""
import os, sys, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import TrainConfig
from repro.configs.paper_models import GPT2_BASE
from repro.data import GlobalBatchLoader
from repro.distributed.sharding import params_pspecs, named_shardings, batch_specs
from repro.checkpoint import CheckpointManager
from repro.models.model import init_params
from repro.optim import adamw_init
from repro.training import make_train_step

phase, ckpt = sys.argv[1], sys.argv[2]
cfg = GPT2_BASE.scaled(name="elastic", n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=4, d_head=16, d_ff=128, vocab_size=128,
                       max_seq=64, dtype="float32")
tcfg = TrainConfig(steps=40, warmup_steps=4, lr=1e-3)
devs = jax.devices()
mesh = jax.sharding.Mesh(np.array(devs), ("data",))
dp = len(devs)
with jax.set_mesh(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0))
    pspecs = params_pspecs(params, model_size=1, dp_size=dp)
    psh = named_shardings(pspecs, mesh)
    params = jax.tree.map(jax.device_put, params, psh)
    opt = adamw_init(params)
    osh = type(opt)(m=psh, v=psh, count=NamedSharding(mesh, P()))
    mgr = CheckpointManager(ckpt, async_write=False)
    start = 0
    if phase == "resume":
        state, meta = mgr.restore_latest({"params": params, "opt": opt},
                                         shardings={"params": psh, "opt": osh})
        params, opt, start = state["params"], state["opt"], meta["step"]
        print(f"[{dp}dev] resumed at step {start}")
    loader = GlobalBatchLoader(cfg, mesh, 16, 32, seed=0)
    bsh = named_shardings(batch_specs(loader.batch_at(0), dp_size=dp), mesh)
    step = jax.jit(make_train_step(cfg, tcfg),
                   in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())))
    end = 20 if phase == "first" else 40
    for i in range(start, end):
        params, opt, m = step(params, opt, loader.batch_at(i), jnp.asarray(i))
        print(f"[{dp}dev] step {i:3d} loss {float(m['total']):.5f}")
    if phase == "first":
        mgr.save(end, {"params": params, "opt": opt}, block=True)
        print(f"[{dp}dev] checkpointed at {end} (simulating node loss)")
"""


def run(phase: str, devices: int, ckpt: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", PHASE_CODE, phase, ckpt],
                         capture_output=True, text=True, env=env, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr)
    return out.stdout


if __name__ == "__main__":
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        print("=== phase 1: 4 devices, steps 0-19, checkpoint, 'crash' ===")
        print(run("first", 4, d))
        print("=== phase 2: resume on 2 devices, steps 20-39 ===")
        print(run("resume", 2, d))
    print("elastic restart OK: trajectory continued on half the devices")
