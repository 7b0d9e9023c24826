"""Pallas TPU kernel: flash attention (causal / sliding-window / bidirectional),
GQA-native.

Online-softmax with (m, l, acc) VMEM scratch carried across the kv grid
dimension. GQA needs no KV repeat in HBM: the K/V BlockSpec index maps query
head ``h`` to kv head ``h // G`` — the broadcast happens in the VMEM copy.
Tiles default to (128 q × 128 k) — MXU-aligned; scores/accumulation fp32.

q: (B, H, T, dh); k, v: (B, KV, S, dh). Causal alignment: the last q row
attends to the last k row (prefill/training layout).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, n_k: int,
            tq: int, tk: int, t_offset: int):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (TQ, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (TK, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qb = pl.program_id(2)
    qpos = qb * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) \
        + t_offset
    kpos = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = jnp.ones((tq, tk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == n_k - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "tq", "tk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    tq: int = 128, tk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Returns (B, H, T, dh); see module docstring for layout."""
    B, H, T, dh = q.shape
    _, KV, S, _ = k.shape
    assert H % KV == 0, (H, KV)
    G = H // KV
    tq, tk = min(tq, T), min(tk, S)
    assert T % tq == 0 and S % tk == 0, (T, tq, S, tk)
    n_q, n_k = T // tq, S // tk
    scale = 1.0 / math.sqrt(dh)
    t_offset = S - T       # causal alignment: last q row ↔ last k row

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, n_k=n_k,
        tq=tq, tk=tk, t_offset=t_offset)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, tq, dh), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, tk, dh),
                         lambda b, h, qb, kb: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, tk, dh),
                         lambda b, h, qb, kb: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, dh),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
