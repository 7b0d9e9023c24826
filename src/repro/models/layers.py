"""Core neural-net layers: inits, norms, RoPE / M-RoPE, attention.

All weights use the ``y = x @ W`` convention, i.e. ``W`` has shape
``(in_dim, out_dim)``. Attention is a chunked flash-style implementation with a
*statically unrolled* block loop: causal block skipping happens in Python, so no
masked-out FLOPs are ever emitted into the HLO (this matters for the roofline
compute term) and the full ``T×S`` score matrix is never materialised (this
matters at 32k/500k context).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, out_dim: int, scale: float = 1.0,
               dtype=jnp.float32) -> jax.Array:
    std = scale / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -3.0, 3.0, (in_dim, out_dim)) * std
            ).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.truncated_normal(key, -3.0, 3.0, (vocab, dim)) * 0.02
            ).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, dim: int, dtype=jnp.float32):
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layer":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (d_head // 2,), float32."""
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, dh); positions: broadcastable to (..., T) int32."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                                   # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv          # (..., T, dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: tuple) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (..., T, H, dh); positions3: (..., T, 3) int32 — (t, h, w) position ids.
    ``sections`` splits the dh/2 frequency channels among the three id streams.
    """
    dh = x.shape[-1]
    assert sum(sections) == dh // 2, (sections, dh)
    inv = rope_freqs(dh, theta)                                   # (dh/2,)
    # pick, per frequency channel, which of the 3 position streams drives it
    sel = jnp.repeat(jnp.arange(3), jnp.array(sections),
                     total_repeat_length=dh // 2)                  # (dh/2,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sel, positions3.shape[:-1] + (dh // 2,)).astype(jnp.int32),
        axis=-1)                                                   # (..., T, dh/2)
    ang = pos * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash-style attention (GQA-native)
# ---------------------------------------------------------------------------
def _block_pair(q_blk, k_blk, v_blk, m, l, acc, scale, mask, p_bf16=False):
    """One (q-block, kv-block) online-softmax update.

    q_blk: (B, Cq, KV, G, dh); k_blk/v_blk: (B, Ck, KV, dh);
    m, l: (B, KV, G, Cq); acc: (B, Cq, KV, G, dh); mask: (Cq, Ck) bool or None.
    """
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale                # (B,KV,G,Cq,Ck)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1)
    if p_bf16:
        # halve the dominant HBM stream: p is in [0,1] so bf16 is safe for
        # the PV contraction (softmax stats m/l stay fp32)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(jnp.bfloat16),
                        v_blk.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p, v_blk.astype(jnp.float32))
    acc = acc * jnp.moveaxis(corr, (1, 2, 3), (2, 3, 1))[..., None] + pv
    return m_new, l, acc


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool, window: int = 0,
              q_offset: int = 0,
              chunk_q: int = 2048, chunk_k: int = 2048,
              p_bf16: bool = False) -> jax.Array:
    """Multi-(grouped-)head attention without materialising T×S scores.

    q: (B, T, H, dh); k, v: (B, S, KV, dh) with H % KV == 0.
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill/decode).
    Returns (B, T, H, dh) in q.dtype.
    """
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, T, KV, G, dh)

    cq = min(chunk_q, T)
    ck = min(chunk_k, S)
    # pad to multiples (masked out below)
    Tp, Sp = -(-T // cq) * cq, -(-S // ck) * ck
    if Tp != T:
        qg = jnp.pad(qg, ((0, 0), (0, Tp - T), (0, 0), (0, 0), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))

    nq, nk = Tp // cq, Sp // ck
    out_blocks = []
    for iq in range(nq):
        q_blk = jax.lax.slice_in_dim(qg, iq * cq, (iq + 1) * cq, axis=1)
        m = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, KV, G, cq), jnp.float32)
        acc = jnp.zeros((B, cq, KV, G, dh), jnp.float32)
        q_lo, q_hi = q_offset + iq * cq, q_offset + (iq + 1) * cq - 1
        for ik in range(nk):
            k_lo, k_hi = ik * ck, (ik + 1) * ck - 1
            if causal and k_lo > q_hi:
                continue                      # static skip: entirely masked
            if window and k_hi < q_lo - window + 1 - (cq - 1):
                continue                      # static skip: beyond the window
            qpos = q_offset + iq * cq + jnp.arange(cq)
            kpos = ik * ck + jnp.arange(ck)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            if Sp != S:
                mask &= kpos[None, :] < S
            full = bool((causal is False) and (window == 0) and (Sp == S))
            k_blk = jax.lax.slice_in_dim(k, ik * ck, (ik + 1) * ck, axis=1)
            v_blk = jax.lax.slice_in_dim(v, ik * ck, (ik + 1) * ck, axis=1)
            m, l, acc = _block_pair(q_blk, k_blk, v_blk, m, l, acc, scale,
                                    None if full else mask, p_bf16=p_bf16)
        l_t = jnp.moveaxis(l, (1, 2, 3), (2, 3, 1))[..., None]     # (B,cq,KV,G,1)
        out_blocks.append(acc / jnp.maximum(l_t, 1e-30))
    out = jnp.concatenate(out_blocks, axis=1)[:, :T]
    return out.reshape(B, T, H, dh).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, window: int = 0,
                     ring: bool = False) -> jax.Array:
    """Single-step attention over a KV cache.

    q: (B, 1, H, dh); k_cache/v_cache: (B, S, KV, dh); cur_len: () or (B,)
    int32 — number of valid cache entries *including* the current token (a
    (B,) vector gives every batch slot its own length — continuous batching).
    With ``ring=True`` the cache is a ring buffer of size S == window
    (positions wrap; masking is by validity only since every live entry is
    inside the window by construction).
    """
    B, _, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(B, KV, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale            # (B,KV,G,S)
    idx = jnp.arange(S)
    cl = jnp.reshape(cur_len, (-1, 1))                             # (1|B, 1)
    valid = idx[None, :] < cl                                      # (1|B, S)
    if window and not ring:
        valid &= idx[None, :] > cl - 1 - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, dh).astype(q.dtype)


def paged_write_targets(pages: jax.Array, pos: jax.Array, n_blocks: int,
                        block_size: int):
    """Where each slot's token at ``pos`` lands in a paged pool: (block,
    offset), both (B,). An unmapped page (-1) redirects the block to
    ``n_blocks``, one past the pool, so a scatter drops the write instead of
    landing it in another slot's block (see ``serving.kv_pages``)."""
    blk, off = pos // block_size, pos % block_size
    page = jnp.take_along_axis(pages, blk[:, None], axis=1)[:, 0]
    return jnp.where(page >= 0, page, n_blocks), off


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pages: jax.Array,
                           cur_len: jax.Array) -> jax.Array:
    """Single-step attention over a *paged* KV cache.

    q: (B, 1, H, dh); k_pool/v_pool: (n_blocks, block_size, KV·dh) — one
    layer's block pool, a position's heads flat (``(…, KV, dh)`` pools work
    too); pages: (B, P) int32 page table (-1 = unmapped; negative indices
    wrap on gather, which is safe because every position ``>= cur_len`` is
    masked and unmapped pages only cover those). The gather materialises
    each slot's (P*block_size) view, then the math is exactly
    :func:`decode_attention` (full-context only — windowed caches stay on
    the dense ring-buffer layout). The serving engine on one device reads
    the pools in place instead (``kernels.paged_attention``); this is that
    kernel's oracle and the sharded engine's path.
    """
    B, P = pages.shape
    bs = k_pool.shape[1]
    dh = q.shape[-1]
    k = k_pool[pages].reshape(B, P * bs, -1, dh)
    v = v_pool[pages].reshape(B, P * bs, -1, dh)
    return decode_attention(q, k, v, cur_len)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, act: str, use_bias: bool,
             n_layers: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], d_model, d_ff, dtype=dtype),
         "w2": dense_init(ks[1], d_ff, d_model, 1.0 / math.sqrt(2 * n_layers),
                          dtype=dtype)}
    if act == "swiglu":
        p["w3"] = dense_init(ks[2], d_model, d_ff, dtype=dtype)
    if use_bias:
        p["b1"] = jnp.zeros((d_ff,), dtype)
        p["b2"] = jnp.zeros((d_model,), dtype)
    return p


def apply_mlp(p, x, act: str):
    h = x @ p["w1"]
    if "b1" in p:
        h = h + p["b1"]
    if act == "swiglu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(h)
    y = h @ p["w2"]
    if "b2" in p:
        y = y + p["b2"]
    return y
