"""Compile the fused LiGO kernels for a described TPU v5e (no chip needed).

Interpret mode (the rest of the suite) checks what the kernels compute;
only the TPU compiler checks that their blocks are legal Mosaic tilings and
that they fit the VMEM limit they ask for. These tests compile the forward
and backward kernels for one chip of a described ``v5e:2x2`` topology at the
BERT-small -> BERT-base group shapes, at the edge of the ``fused_eligible``
budget, and just past it. A compile here is not a run: it says nothing of
results or times.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ligo_expand import (VMEM_LIMIT_BYTES, fused_eligible,
                                       fused_vmem_bytes,
                                       ligo_blend_expand_grouped)
from repro.kernels.ligo_expand_bwd import ligo_blend_expand_bwd_fused


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
