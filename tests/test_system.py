"""End-to-end behaviour tests: the full grow→train pipeline on every
assigned architecture family plus the paper's BERT growth recipe."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, grow_target, smoke_config
from repro.configs.base import TrainConfig
from repro.configs.paper_models import BERT_SMALL
from repro.core import apply_ligo, grow, init_ligo_params
from repro.data import batch_for_step, optimal_loss
from repro.models import init_params, loss_fn
from repro.models.inputs import dummy_batch
from repro.training import init_train_state, make_train_step

TINY_GPT = BERT_SMALL.scaled(
    name="tiny-clm", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="float32",
    objective="clm", encoder_only=False, causal=True)


def test_end_to_end_grow_then_train():
    """The paper's pipeline: pretrain small → learn LiGO → grow → train."""
    cfg1 = TINY_GPT
    cfg2 = cfg1.scaled(name="tiny-clm-big", n_layers=4, d_model=48, d_head=12,
                       d_ff=96)
    tcfg = TrainConfig(steps=30, warmup_steps=5, lr=1e-3)
    params, opt = init_train_state(cfg1, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg1, tcfg))
    for i in range(30):
        b = {k: jnp.asarray(v)
             for k, v in batch_for_step(cfg1, i, 8, 32, seed=0).items()}
        params, opt, m = step(params, opt, b, jnp.asarray(i))

    it = ({k: jnp.asarray(v)
           for k, v in batch_for_step(cfg1, 1000 + s, 8, 32, seed=0).items()}
          for s in itertools.count())
    big, info = grow(params, cfg1, cfg2, method="ligo", data_it=it,
                     ligo_steps=5, ligo_lr=1e-3)
    assert "ligo_losses" in info and len(info["ligo_losses"]) == 5

    tcfg2 = TrainConfig(steps=10, warmup_steps=2, lr=1e-3)
    from repro.optim import adamw_init
    opt2 = adamw_init(big)
    step2 = jax.jit(make_train_step(cfg2, tcfg2))
    b = {k: jnp.asarray(v)
         for k, v in batch_for_step(cfg2, 0, 8, 32, seed=0).items()}
    big2, opt2, m = step2(big, opt2, b, jnp.asarray(0))
    assert np.isfinite(float(m["total"]))


@pytest.mark.parametrize("method", ["stackbert", "interpolation", "net2net",
                                    "bert2bert", "random"])
def test_grow_methods_produce_trainable_models(method):
    cfg1 = TINY_GPT
    cfg2 = (cfg1.scaled(name="deep", n_layers=4) if method in
            ("stackbert", "interpolation")
            else cfg1.scaled(name="wide", n_layers=4, d_model=64, n_heads=8,
                             n_kv_heads=8, d_head=8, d_ff=128))
    small = init_params(cfg1, jax.random.PRNGKey(0))
    big, _ = grow(small, cfg1, cfg2, method=method,
                  key=jax.random.PRNGKey(1))
    b = dummy_batch(cfg2, 2, 16, "train")
    loss, _ = loss_fn(big, cfg2, b)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_grow_every_assigned_family(arch):
    c1 = smoke_config(ASSIGNED[arch])
    c2 = grow_target(c1)
    p1 = init_params(c1, jax.random.PRNGKey(0))
    lg = init_ligo_params(jax.random.PRNGKey(1), c1, c2)
    p2 = apply_ligo(lg, p1, c1, c2)
    ref_shapes = jax.tree.map(lambda a: a.shape,
                              init_params(c2, jax.random.PRNGKey(0)))
    got_shapes = jax.tree.map(lambda a: a.shape, p2)
    assert ref_shapes == got_shapes
    loss, _ = loss_fn(p2, c2, dummy_batch(c2, 2, 16, "train"))
    assert np.isfinite(float(loss))


def test_serve_hot_grow_smoke(monkeypatch, capsys):
    """Growth-time elastic serving: --grow-to hot-grows the checkpoint at
    startup through the cached GrowthPlan executor and serves the grown
    architecture end-to-end (prefill + decode)."""
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3-8b", "--smoke", "--grow-to", "2x",
        "--batch", "1", "--prompt-len", "8", "--gen", "3"])
    serve.main()
    out = capsys.readouterr().out
    assert "hot-grew" in out and "-grown" in out
    assert "tok/s" in out          # decode ran on the grown model


def test_serve_hot_grow_multihop_composed(monkeypatch, capsys):
    """--grow-to with a multi-hop list ('2x,4x') routes through the composed
    operator: ONE fused plan apply to the final arch (no intermediate
    model), and the result equals growing hop-by-hop."""
    import sys
    from repro.configs import get_config, grow_target, smoke_config
    from repro.core import apply_ligo, init_ligo_params
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3-8b", "--smoke", "--grow-to", "2x,4x",
        "--batch", "1", "--prompt-len", "8", "--gen", "3"])
    serve.main()
    out = capsys.readouterr().out
    assert "via 2 composed hops (one fused apply)" in out
    assert "-grown-grown" in out and "tok/s" in out

    # composed hot_grow == sequential hop-by-hop growth (same seeds)
    cfg = smoke_config(get_config("llama3-8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    grown, cfg2 = serve.hot_grow(params, cfg, "2x,4x", smoke=True)
    mid_cfg = grow_target(cfg)
    assert cfg2.name == grow_target(mid_cfg).name
    mid = apply_ligo(init_ligo_params(jax.random.PRNGKey(1), cfg, mid_cfg),
                     params, cfg, mid_cfg)
    want = apply_ligo(
        init_ligo_params(jax.random.PRNGKey(2), mid_cfg, cfg2),
        mid, mid_cfg, cfg2)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(grown)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_training_converges_toward_process_entropy():
    cfg = TINY_GPT.scaled(name="conv", d_model=64, d_head=16, d_ff=128,
                          vocab_size=128)
    tcfg = TrainConfig(steps=100, warmup_steps=10, lr=3e-3)
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    losses = []
    for i in range(100):
        b = {k: jnp.asarray(v)
             for k, v in batch_for_step(cfg, i, 16, 32, seed=0).items()}
        params, opt, m = step(params, opt, b, jnp.asarray(i))
        losses.append(float(m["total"]))
    assert losses[-1] < losses[0] - 1.5
    assert losses[-1] < np.log(128) * 0.6          # well below uniform
    assert losses[-1] > optimal_loss(128) * 0.5    # and sane


def test_compile_cache_dir(subproc, tmp_path, monkeypatch):
    """The entry points' compile cache: with JAX_COMPILATION_CACHE_DIR set
    the programs land in that directory and the setting is left alone;
    without it the cache is the fixed in-checkout directory."""
    code = """
import os, jax, jax.numpy as jnp
from repro.launch import compile_cache
from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
got = use_compile_cache()
if env:
    assert got == env and jax.config.jax_compilation_cache_dir == env
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    assert os.listdir(env), "nothing cached in JAX_COMPILATION_CACHE_DIR"
else:
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(compile_cache.__file__))))
    root = os.path.dirname(src)
    assert got == CACHE_DIR == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == CACHE_DIR
print("CACHE_OK")
"""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert "CACHE_OK" in subproc(code, n_devices=1)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert "CACHE_OK" in subproc(code, n_devices=1)
