"""Residual blocks: attention (+MLP), MoE, mLSTM, sLSTM, Mamba2.

Each kind exposes ``init_<kind>(key, cfg, dtype)`` returning one layer's params
and ``apply_<kind>(p, x, cfg, ...)`` with three modes:

- train/prefill: full-sequence mixing; prefill additionally returns the cache
  contribution (K/V or recurrent state) for subsequent decode.
- decode: single-token step against a cache/state.

Cache layout (per layer): attention ``{"k","v"}: (B, S, KV, dh)``; Mamba2/mLSTM
``{"conv": (B, K-1, C), "S": (B,H,dk,dv), "n": (B,H,dk)}``; sLSTM
``{"h","c","n","m"}: (B, D)``. Stacked over layers by the model-level scan.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import P, maybe_shard
from repro.kernels import ops as kernel_ops
from repro.models import seqmix
from repro.models.layers import (apply_mlp, apply_norm, apply_mrope, apply_rope,
                                 attention, decode_attention, dense_init,
                                 init_mlp, init_norm, paged_decode_attention,
                                 paged_write_targets)
from repro.models.moe import apply_moe, init_moe


def _use_bias(cfg) -> bool:
    return cfg.norm == "layer"


# ---------------------------------------------------------------------------
# Attention block (dense MLP or none)
# ---------------------------------------------------------------------------
def init_attn(key, cfg, dtype=jnp.float32):
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 6)
    p = {
        "ln1": init_norm(cfg.norm, D, dtype),
        "wq": dense_init(ks[0], D, H * dh, dtype=dtype),
        "wk": dense_init(ks[1], D, KV * dh, dtype=dtype),
        "wv": dense_init(ks[2], D, KV * dh, dtype=dtype),
        "wo": dense_init(ks[3], H * dh, D, 1.0 / math.sqrt(2 * cfg.n_layers),
                         dtype=dtype),
        "ln2": init_norm(cfg.norm, D, dtype),
    }
    if _use_bias(cfg):
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
        p["bo"] = jnp.zeros((D,), dtype)
    if cfg.d_ff > 0:
        p["mlp"] = init_mlp(ks[4], D, cfg.d_ff, cfg.act, _use_bias(cfg),
                            cfg.n_layers, dtype)
    return p


def _qkv(p, h, cfg, positions):
    B, T, _ = h.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = h @ p["wq"] + (p["bq"] if "bq" in p else 0.0)
    k = h @ p["wk"] + (p["bk"] if "bk" in p else 0.0)
    v = h @ p["wv"] + (p["bv"] if "bv" in p else 0.0)
    q = maybe_shard(q.reshape(B, T, H, dh), P("data", None, "model", None))
    k = maybe_shard(k.reshape(B, T, KV, dh), P("data", None, None, None))
    v = maybe_shard(v.reshape(B, T, KV, dh), P("data", None, None, None))
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def apply_attn(p, x, cfg, positions, *, mode: str = "train",
               cache: Optional[dict] = None, cur_len=None,
               chunk_q: int = 2048, chunk_k: int = 2048,
               p_bf16: bool = False, pages=None, layer=None):
    """Returns (x_out, new_cache_or_None, aux_loss).

    ``pages`` (decode only): a (B, P) int32 page table switching the cache
    to the paged layout — ``cache`` leaves are then block pools
    ``(n_blocks, block_size, KV·dh)`` shared across slots, written through
    the table (unmapped targets are dropped, see ``serving.kv_pages``) and
    read via :func:`paged_decode_attention`. Requires per-slot ``cur_len``
    ((B,)) and full-context attention (no window).

    ``layer`` (with ``pages``): ``cache`` leaves are the whole stacked pools
    ``(L, n_blocks, block_size, KV·dh)`` and the paged-attention kernel
    reads this layer's live pages in place; the returned cache is the new
    token's ``{"k", "v"}: (B, KV·dh)``, which the caller writes into the
    pools after the layer loop.
    """
    B, T, D = x.shape
    h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
    new_cache = None
    if mode == "decode" and layer is not None:
        q, k, v = _qkv(p, h, cfg, positions)              # T == 1
        lens = jnp.broadcast_to(cur_len - 1, (B,))
        o = kernel_ops.paged_attention(
            q[:, 0], k[:, 0], v[:, 0], cache["k"], cache["v"], layer,
            pages, lens)[:, None]
        new_cache = {"k": k[:, 0].reshape(B, -1), "v": v[:, 0].reshape(B, -1)}
    elif mode == "decode" and pages is not None:
        assert not cfg.window, "paged KV requires full-context attention"
        q, k, v = _qkv(p, h, cfg, positions)              # T == 1
        n_blocks, bs = cache["k"].shape[:2]
        tgt, off = paged_write_targets(pages, (cur_len - 1).astype(jnp.int32),
                                       n_blocks, bs)
        k_cache = cache["k"].at[tgt, off].set(k[:, 0].reshape(B, -1))
        v_cache = cache["v"].at[tgt, off].set(v[:, 0].reshape(B, -1))
        o = paged_decode_attention(q, k_cache, v_cache, pages, cur_len)
        new_cache = {"k": k_cache, "v": v_cache}
    elif mode == "decode":
        q, k, v = _qkv(p, h, cfg, positions)              # T == 1
        S = cache["k"].shape[1]
        ring = bool(cfg.window) and S == cfg.window
        slot = ((cur_len - 1) % S if ring else (cur_len - 1)).astype(jnp.int32)
        if slot.ndim:
            # per-slot write positions (continuous batching): each batch row
            # lands its token at its own sequence offset
            upd = jax.vmap(
                lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))
            k_cache = upd(cache["k"], k, slot)
            v_cache = upd(cache["v"], v, slot)
        else:
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k, (0, slot, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v, (0, slot, 0, 0))
        o = decode_attention(q, k_cache, v_cache, cur_len,
                             window=cfg.window, ring=ring)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        q, k, v = _qkv(p, h, cfg, positions)
        o = attention(q, k, v, causal=cfg.causal and not cfg.encoder_only,
                      window=cfg.window, chunk_q=chunk_q, chunk_k=chunk_k,
                      p_bf16=p_bf16)
        if mode == "prefill":
            S = cfg.window if (cfg.window and cfg.window < T) else T
            # ring-buffer layout: token t lives at slot t % S (so decode's
            # `(cur_len-1) % S` slot assignment continues seamlessly)
            new_cache = {"k": jnp.roll(k[:, -S:], T % S, axis=1),
                         "v": jnp.roll(v[:, -S:], T % S, axis=1)}
    o = o.reshape(B, T, -1) @ p["wo"] + (p["bo"] if "bo" in p else 0.0)
    x = x + maybe_shard(o, P("data", None, None))
    aux = jnp.zeros((), jnp.float32)
    if "mlp" in p:
        h2 = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h2, cfg.act)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# MoE block (attention + expert MLP)
# ---------------------------------------------------------------------------
def init_moe_block(key, cfg, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p = init_attn(k1, cfg, dtype)
    p.pop("mlp", None)
    p["moe"] = init_moe(k2, cfg, dtype)
    return p


def apply_moe_block(p, x, cfg, positions, *, mode="train", cache=None,
                    cur_len=None, chunk_q=2048, chunk_k=2048, p_bf16=False,
                    pages=None, layer=None):
    # attention sub-block (reuse apply_attn without its MLP)
    p_attn = {k: v for k, v in p.items() if k != "moe"}
    x, new_cache, _ = apply_attn(p_attn, x, cfg, positions, mode=mode,
                                 cache=cache, cur_len=cur_len,
                                 chunk_q=chunk_q, chunk_k=chunk_k,
                                 p_bf16=p_bf16, pages=pages, layer=layer)
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if cfg.moe_impl == "shard_map":
        from repro.models.moe_shardmap import (apply_moe_shardmap,
                                               moe_shardmap_available)
        if moe_shardmap_available(cfg, batch_size=h.shape[0]):
            y, aux = apply_moe_shardmap(p["moe"], h, cfg)
            return x + y, new_cache, aux
    y, aux = apply_moe(p["moe"], h, cfg)
    return x + y, new_cache, aux


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM matrix memory)
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg, dtype=jnp.float32):
    D = cfg.d_model
    di = cfg.ssm_expand * D
    H = cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "ln": init_norm(cfg.norm, D, dtype),
        "up": dense_init(ks[0], D, 2 * di, dtype=dtype),
        "conv": (jax.random.normal(ks[1], (cfg.conv_kernel, di)) * 0.02
                 ).astype(dtype),
        "wqkv": dense_init(ks[2], di, 3 * di, dtype=dtype),
        "gates": dense_init(ks[3], di, 2 * H, dtype=dtype),
        "gates_b": jnp.concatenate([jnp.zeros((H,), dtype),
                                    jnp.linspace(3.0, 6.0, H).astype(dtype)]),
        "down": dense_init(ks[4], di, D, 1.0 / math.sqrt(2 * cfg.n_layers),
                           dtype=dtype),
    }


def apply_mlstm(p, x, cfg, *, mode="train", cache=None):
    B, T, D = x.shape
    di = cfg.ssm_expand * D
    H = cfg.n_heads
    dh = di // H
    h = apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    u = h @ p["up"]
    xi, z = jnp.split(u, 2, axis=-1)                       # (B,T,di) each
    conv_state = cache.get("conv") if cache else None
    xi, conv_new = seqmix.causal_conv(xi, p["conv"], conv_state)
    xi = jax.nn.silu(xi)
    qkv = xi @ p["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, dh)
    k = k.reshape(B, T, H, dh) / math.sqrt(dh)
    v = v.reshape(B, T, H, dh)
    g = xi @ p["gates"] + p["gates_b"]                     # (B,T,2H)
    log_i = jax.nn.log_sigmoid(g[..., :H])
    log_f = jax.nn.log_sigmoid(g[..., H:])
    if mode == "decode":
        state = seqmix.GLAState(cache["S"], cache["n"])
        o, new_state = seqmix.gla_step(q[:, 0], k[:, 0], v[:, 0],
                                       log_f[:, 0], log_i[:, 0], state,
                                       normalize=True)
        o = o[:, None]                                     # (B,1,H,dh)
    else:
        state = (seqmix.GLAState(cache["S"], cache["n"]) if cache else None)
        o, new_state = seqmix.gla_chunked(q, k, v, log_f, log_i, state,
                                          normalize=True)
    o = o.reshape(B, T, di) * jax.nn.silu(z)
    y = o @ p["down"]
    new_cache = {"conv": conv_new, "S": new_state.S, "n": new_state.n}
    return x + y, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# sLSTM block (xLSTM scalar memory)
# ---------------------------------------------------------------------------
def init_slstm(key, cfg, dtype=jnp.float32):
    D = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "ln": init_norm(cfg.norm, D, dtype),
        "w": dense_init(ks[0], D, 4 * D, dtype=dtype),
        "r": dense_init(ks[1], D, 4 * D, dtype=dtype),
        "b": jnp.zeros((4 * D,), dtype),
        "out": dense_init(ks[2], D, D, 1.0 / math.sqrt(2 * cfg.n_layers),
                          dtype=dtype),
    }


def apply_slstm(p, x, cfg, *, mode="train", cache=None):
    B, T, D = x.shape
    h = apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    if cache is not None:
        state = seqmix.SLSTMState(cache["h"], cache["c"], cache["n"],
                                  cache["m"])
    else:
        state = seqmix.slstm_init_state(B, D, jnp.float32)
    if mode == "decode":
        xg = (h @ p["w"])[:, 0]
        o, new_state = seqmix.slstm_cell(xg, p, state)
        o = o[:, None]
    else:
        o, new_state = seqmix.slstm_seq(h, p, state)
    y = o @ p["out"]
    new_cache = {"h": new_state.h, "c": new_state.c, "n": new_state.n,
                 "m": new_state.m}
    return x + y, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Mamba2 block (SSD)
# ---------------------------------------------------------------------------
def init_mamba2(key, cfg, dtype=jnp.float32):
    D = cfg.d_model
    di = cfg.ssm_expand * D
    H = cfg.mamba_heads
    N = cfg.ssm_state
    ks = jax.random.split(key, 4)
    conv_ch = di + 2 * N                                    # conv over [x, B, C]
    return {
        "ln": init_norm(cfg.norm, D, dtype),
        "in_proj": dense_init(ks[0], D, 2 * di + 2 * N + H, dtype=dtype),
        "conv": (jax.random.normal(ks[1], (cfg.conv_kernel, conv_ch)) * 0.02
                 ).astype(dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "Dskip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01))).astype(jnp.float32),
        "gn": init_norm("rms", di, dtype),
        "out_proj": dense_init(ks[2], di, D, 1.0 / math.sqrt(2 * cfg.n_layers),
                               dtype=dtype),
    }


def apply_mamba2(p, x, cfg, *, mode="train", cache=None):
    B, T, D = x.shape
    di = cfg.ssm_expand * D
    H = cfg.mamba_heads
    N = cfg.ssm_state
    dh = di // H
    h = apply_norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    u = h @ p["in_proj"]                                   # (B,T,2di+2N+H)
    z, xbc, dt = (u[..., :di], u[..., di:di + di + 2 * N],
                  u[..., di + di + 2 * N:])
    conv_state = cache.get("conv") if cache else None
    xbc, conv_new = seqmix.causal_conv(xbc, p["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs, Bc, Cc = (xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,H)
    log_f = -jnp.exp(p["A_log"]) * dt                             # ≤ 0
    v = xs.reshape(B, T, H, dh) * dt[..., None].astype(xs.dtype)
    k = jnp.broadcast_to(Bc[:, :, None], (B, T, H, N))
    q = jnp.broadcast_to(Cc[:, :, None], (B, T, H, N))
    log_i = jnp.zeros_like(log_f)
    if mode == "decode":
        state = seqmix.GLAState(cache["S"], cache["n"])
        o, new_state = seqmix.gla_step(q[:, 0], k[:, 0], v[:, 0],
                                       log_f[:, 0], log_i[:, 0], state)
        o = o[:, None]
    else:
        state = (seqmix.GLAState(cache["S"], cache["n"]) if cache else None)
        o, new_state = seqmix.gla_chunked(q, k, v, log_f, log_i, state)
    xs_h = xs.reshape(B, T, H, dh)
    if mode == "decode":
        xs_h = xs_h[:, :1]
    o = o + xs_h * p["Dskip"][:, None].astype(o.dtype)     # D·x skip connection
    o = o.reshape(B, T, di) * jax.nn.silu(z)
    o = apply_norm(p["gn"], o, "rms")
    y = o @ p["out_proj"]
    new_cache = {"conv": conv_new, "S": new_state.S, "n": new_state.n}
    return x + y, new_cache, jnp.zeros((), jnp.float32)


INIT = {"attn": init_attn, "moe": init_moe_block, "mlstm": init_mlstm,
        "slstm": init_slstm, "mamba2": init_mamba2, "shared_attn": init_attn}
