"""Compile the kernels and the paged decode round for a described TPU v5e
(no chip needed).

Interpret mode (the rest of the suite) checks what the kernels compute;
only the TPU compiler checks that their blocks are legal Mosaic tilings and
that they fit the VMEM limit they ask for. These tests compile the forward
and backward kernels for one chip of a described ``v5e:2x2`` topology at the
BERT-small -> BERT-base group shapes, at the edge of the ``fused_eligible``
budget, and just past it; and the paged-attention kernel at GPT-2's and
GPT-2 medium's serving shapes, with the decode program around it. A compile
here is not a run: it says nothing of results or times.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ligo_expand import (VMEM_LIMIT_BYTES, fused_eligible,
                                       fused_vmem_bytes,
                                       ligo_blend_expand_grouped)
from repro.kernels.ligo_expand_bwd import ligo_blend_expand_bwd_fused
from repro.kernels.paged_attention import page_fits, paged_attention


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: entries compiled for an absent chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(sharding, kernel, G, L1, L2, E, I, A, Bd, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    args = [sds((G, L2, L1), jnp.float32), sds((I, A), dtype),
            sds((G, L1, E, A, Bd), dtype)]
    if kernel == "fwd":
        fn = lambda w, B, W: ligo_blend_expand_grouped(w, B, W)  # noqa: E731
    else:
        args.append(sds((G, L2, E, I, Bd), dtype))
        fn = lambda w, B, W, dP: ligo_blend_expand_bwd_fused(  # noqa: E731
            w, B, W, dP)
    return jax.jit(fn).lower(*args).compile()


# BERT-small (6L, d=512, ff=2048) -> BERT-base (12L, d=768): the attention
# projections wq/wk/wv/wo (Bd = 512) and mlp/w1 (Bd = 2048), one leaf each.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Bd", [512, 2048], ids=["attn", "mlp_w1"])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_bert_group_compiles_for_v5e(one_chip, kernel, Bd, dtype):
    shape = dict(G=1, L1=6, L2=12, E=1, I=768, A=512, Bd=Bd)
    assert fused_eligible(shape["L1"], shape["L2"], 1, shape["I"],
                          shape["A"], Bd, G=1,
                          itemsize=jnp.dtype(dtype).itemsize)
    compiled = _compile(one_chip, kernel, dtype=dtype, **shape)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_edge_of_budget_compiles_for_v5e(one_chip, kernel):
    """A shape the predicate admits with less than 2% of the limit to spare
    compiles under the limit the kernels ask for."""
    L1, L2, I, A, Bd = 6, 12, 768, 512, 512
    need = fused_vmem_bytes(L1, L2, I, A, Bd, G=1, itemsize=4)
    assert 0.98 * VMEM_LIMIT_BYTES <= need <= VMEM_LIMIT_BYTES
    assert fused_eligible(L1, L2, 1, I, A, Bd, G=1, itemsize=4)
    compiled = _compile(one_chip, kernel, 1, L1, L2, 1, I, A, Bd,
                        jnp.float32)
    assert "tpu_custom_call" in compiled.as_text()


def test_refused_shape_overflows_vmem_on_v5e(one_chip):
    """GPT2-base -> GPT2-medium q/k/v (three leaves, bf16): the predicate
    refuses it, and the compiler refuses the backward kernel for VMEM."""
    G, L1, L2, I, A, Bd = 3, 12, 24, 1024, 768, 768
    assert not fused_eligible(L1, L2, 1, I, A, Bd, G=G, itemsize=2)
    with pytest.raises(Exception, match="vmem"):
        _compile(one_chip, "bwd", G, L1, L2, 1, I, A, Bd, jnp.bfloat16)


# Serving shapes of the gpt2.serve_hop cell: 32 slots, 64 pages of 16
# positions each, bf16 pools (GPT-2: 12 layers, 12 kv heads; GPT-2 medium:
# 24 and 16), and a grouped-query shape (32 query heads on 8 kv heads).
@pytest.mark.parametrize("L,H,KV", [(12, 12, 12), (24, 16, 16), (4, 32, 8)],
                         ids=["gpt2", "gpt2-medium", "gqa"])
def test_paged_attention_compiles_for_v5e(one_chip, L, H, KV):
    B, P, bs, dh = 32, 64, 16, 64

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert page_fits(bs, KV * dh)
    pool = sds((L, B * P, bs, KV * dh))
    compiled = jax.jit(paged_attention).lower(
        sds((B, H, dh)), sds((B, KV, dh)), sds((B, KV, dh)), pool, pool,
        sds((), jnp.int32), sds((B, P), jnp.int32),
        sds((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bs,KV,dh", [(4, 16, 64), (16, 1, 64)],
                         ids=["block4", "features64"])
def test_paged_attention_refuses_untiled_pages_on_v5e(one_chip, bs, KV, dh):
    """Pages that are not whole (8, 128) tiles: ``page_fits`` refuses them
    (the engine then gathers), and so does the compiler."""
    L, B, P = 2, 8, 16
    assert not page_fits(bs, KV * dh)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((L, B * P, bs, KV * dh))
    with pytest.raises(Exception, match="aligned"):
        jax.jit(paged_attention).lower(
            sds((B, KV, dh)), sds((B, KV, dh)), sds((B, KV, dh)), pool, pool,
            sds((), jnp.int32), sds((B, P), jnp.int32),
            sds((B,), jnp.int32)).compile()


def test_decode_round_writes_pools_in_place_on_v5e(one_chip, monkeypatch):
    """The one-device decode program, compiled for the chip: the kernel is
    in it, the donated pools are its outputs' buffers, it needs no
    temporary of a pool's size, and no array of the pools' block count
    exists in float32 (the gather path converted the whole pool)."""
    from repro.configs.paper_models import BERT_SMALL
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving.engine import make_serving_fns
    from repro.serving.kv_pages import init_paged_caches
    # the program asks the default backend (the CPU here) whether to
    # interpret the kernel; this compile is for the TPU
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = BERT_SMALL.scaled(
        name="tpu-paged", n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
        d_head=64, d_ff=512, vocab_size=512, max_seq=256, dtype="bfloat16",
        objective="clm", encoder_only=False, causal=True)
    B, P, bs, n_blocks = 8, 16, 16, 200

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = sds(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    caches = sds(jax.eval_shape(
        lambda: init_paged_caches(cfg, n_blocks, bs)))
    state = {"caches": caches,
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
             "pages": jax.ShapeDtypeStruct((B, P), jnp.int32,
                                           sharding=one_chip)}
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    _, decode, _ = make_serving_fns(cfg, P * bs, "paged", False, True)
    compiled = decode.lower(params, state, toks).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // cfg.n_layers
    f32_pool = re.findall(rf"f32\[[0-9,]*\b{n_blocks}\b[0-9,]*\]", text)
    assert not f32_pool, f32_pool
