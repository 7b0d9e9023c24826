"""Unified model API over every architecture family.

``init_params(cfg, key)`` → parameter pytree (layer params stacked over a
leading L dim for lax.scan). ``forward(...)`` runs train / prefill / decode.
Layer loops are ``lax.scan`` over stacked parameters (compile-time friendly at
62–80 layers on 512-device meshes); heterogeneous families scan over
super-blocks (xLSTM: [mLSTM, sLSTM] pairs; Zamba2: groups of ``k`` Mamba2
layers followed by the shared attention block, whose K/V caches are stacked
per-group since the tied block is applied at G distinct depths).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import P, maybe_shard
from repro.models import blocks as B
from repro.models.layers import (apply_norm, embed_init, init_norm,
                                 paged_write_targets)

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _dtype(cfg):
    return DTYPES[cfg.dtype]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = _dtype(cfg)
    k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
    params: Dict[str, Any] = {"embed": {}, "layers": {}}

    if cfg.modality not in ("audio", "vision"):
        params["embed"]["tok"] = embed_init(k_emb, cfg.vocab_size, cfg.d_model,
                                            dtype)
    if cfg.modality == "audio":
        params["embed"]["mask_emb"] = (
            jax.random.normal(k_emb, (cfg.d_model,)) * 0.02).astype(dtype)
    if cfg.modality == "vision":
        params["embed"]["cls"] = (
            jax.random.normal(k_emb, (cfg.d_model,)) * 0.02).astype(dtype)
    if cfg.rope == "learned":
        params["embed"]["pos"] = embed_init(k_extra, cfg.max_seq, cfg.d_model,
                                            dtype)

    # --- layer stacks, grouped by block kind (pattern order preserved) ---
    kinds = cfg.blocks
    stacks: Dict[str, int] = {}
    for k in kinds:
        stacks[k] = stacks.get(k, 0) + 1
    layer_keys = jax.random.split(k_layers, len(stacks) + 1)
    for i, (kind, count) in enumerate(sorted(stacks.items())):
        init_one = functools.partial(B.INIT[kind], cfg=cfg, dtype=dtype)
        params["layers"][kind] = jax.vmap(lambda kk: init_one(kk))(
            jax.random.split(layer_keys[i], count))
    if cfg.family == "hybrid":
        # single shared attention block (parameter-tied across insertions)
        params["layers"]["shared_attn"] = B.init_attn(layer_keys[-1], cfg,
                                                      dtype)

    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dtype)
    tied = cfg.tie_embeddings and "tok" in params["embed"]
    if not tied:
        params["head"] = embed_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
          offset=0) -> Tuple[jax.Array, Any]:
    """Returns (x (B,T,D), rope_positions)."""
    emb = params["embed"]
    if cfg.modality == "audio":
        x = batch["frames"].astype(_dtype(cfg))
        if "mask" in batch:
            x = jnp.where(batch["mask"][..., None], emb["mask_emb"], x)
        T = x.shape[1]
    elif cfg.modality == "vision":
        patches = batch["patches"].astype(_dtype(cfg))
        cls = jnp.broadcast_to(emb["cls"], (patches.shape[0], 1, cfg.d_model))
        x = jnp.concatenate([cls, patches], axis=1)
        T = x.shape[1]
    else:
        tokens = batch["tokens"]
        x = jnp.take(emb["tok"], tokens, axis=0)
        T = tokens.shape[1]
        if cfg.modality == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            np_ = pe.shape[1]
            x = jnp.concatenate([pe, x[:, np_:]], axis=1)
    # offset: () for lock-step decode, (B,) for per-slot positions
    # (continuous batching — each batch row at its own sequence offset)
    off = jnp.asarray(offset)
    if cfg.rope == "learned":
        idx = jnp.arange(T) + (off[:, None] if off.ndim else off)
        x = x + jnp.take(emb["pos"], idx, axis=0)
    if cfg.rope == "mrope":
        positions = batch["positions"]            # (B, T, 3)
    else:                                         # (1|B, T), broadcasts over B
        positions = jnp.arange(T)[None] + (off[:, None] if off.ndim else off)
    x = maybe_shard(x, P("data", None, None))
    return x, positions


def unembed(params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    if cfg.tie_embeddings and "tok" in params["embed"]:
        return hidden @ params["embed"]["tok"].T
    return hidden @ params["head"]


# ---------------------------------------------------------------------------
# Layer-stack engines
# ---------------------------------------------------------------------------
_APPLY = {"attn": B.apply_attn, "moe": B.apply_moe_block}
_SEQ_APPLY = {"mlstm": B.apply_mlstm, "slstm": B.apply_slstm,
              "mamba2": B.apply_mamba2}


def _maybe_remat(fn, remat: bool):
    return jax.checkpoint(fn) if remat else fn


def _fwd_homogeneous(params, x, cfg, positions, *, mode, caches, cur_len,
                     remat, chunk_q, chunk_k, act_spec=None, p_bf16=False,
                     pages=None, paged_kernel=False):
    kind = cfg.blocks[0]
    # paged decode with the kernel: the layers read the stacked pools in
    # place (the scan walks layer indices, not pool slices) and hand back
    # their new token's K/V, written into the pools once after the loop
    in_place = mode == "decode" and pages is not None and paged_kernel

    def body(carry, inp):
        h, aux = carry
        p, c = inp
        layer = None
        if in_place:
            c, layer = caches, c
        if kind in _APPLY:
            h, nc, a = _APPLY[kind](p, h, cfg, positions, cache=c, mode=mode,
                                    cur_len=cur_len, chunk_q=chunk_q,
                                    chunk_k=chunk_k, p_bf16=p_bf16,
                                    pages=pages, layer=layer)
        else:
            h, nc, a = _SEQ_APPLY[kind](p, h, cfg, mode=mode, cache=c)
        if act_spec is not None:
            h = maybe_shard(h, act_spec)
        if mode == "train":
            nc = None
        return (h, aux + a), nc

    per_layer = jnp.arange(caches["k"].shape[0]) if in_place else caches
    (x, aux), new_caches = jax.lax.scan(
        _maybe_remat(body, remat), (x, jnp.zeros((), jnp.float32)),
        (params["layers"][kind], per_layer))
    if in_place:
        new_caches = _write_step_paged(caches, pages, cur_len - 1, new_caches)
    return x, new_caches, aux


def _write_step_paged(pools, pages, pos, toks):
    """Every layer's new token into the stacked pools, one scatter per pool:
    pools ``{"k","v"}: (L, n_blocks, bs, F)``, toks ``(L, B, F)``, pos (B,).
    Unmapped targets are dropped."""
    L, n_blocks, bs = pools["k"].shape[:3]
    tgt, off = paged_write_targets(pages, pos.astype(jnp.int32), n_blocks, bs)
    # every (layer, slot) indexed, one F-wide row each: a window over the
    # layer dim would make XLA relayout the whole pool around the scatter
    layer = jnp.arange(L)[:, None]
    return {n: pools[n].at[layer, tgt[None], off[None]].set(toks[n])
            for n in ("k", "v")}


def _fwd_xlstm(params, x, cfg, *, mode, caches, remat, act_spec=None):
    # pattern = (mlstm, slstm) pairs; scan over L/2 super-blocks
    def body(carry, inp):
        h = carry
        (pm, ps), (cm, cs) = inp
        h, ncm, _ = B.apply_mlstm(pm, h, cfg, mode=mode, cache=cm)
        h, ncs, _ = B.apply_slstm(ps, h, cfg, mode=mode, cache=cs)
        if act_spec is not None:
            h = maybe_shard(h, act_spec)
        if mode == "train":
            ncm = ncs = None
        return h, (ncm, ncs)

    xs = ((params["layers"]["mlstm"], params["layers"]["slstm"]),
          caches if caches is not None else (None, None))
    x, new_caches = jax.lax.scan(_maybe_remat(body, remat), x, xs)
    return x, new_caches, jnp.zeros((), jnp.float32)


def _fwd_zamba(params, x, cfg, positions, *, mode, caches, cur_len, remat,
               chunk_q, chunk_k, act_spec=None):
    k = cfg.shared_attn_every
    L = cfg.n_layers
    assert L % k == 0, (L, k)
    G = L // k
    p_a = params["layers"]["shared_attn"]
    p_mg = jax.tree.map(lambda a: a.reshape((G, k) + a.shape[1:]),
                        params["layers"]["mamba2"])
    if caches is None:
        c_mg, c_ag = None, None
    else:
        c_m, c_ag = caches               # attn caches stacked (G, ...)
        c_mg = jax.tree.map(lambda a: a.reshape((G, k) + a.shape[1:]), c_m)

    def body(carry, inp):
        h = carry
        pg, cg, cag = inp
        ncg = []
        for j in range(k):
            pj = jax.tree.map(lambda a: a[j], pg)
            cj = None if cg is None else jax.tree.map(lambda a: a[j], cg)
            h, ncj, _ = B.apply_mamba2(pj, h, cfg, mode=mode, cache=cj)
            ncg.append(ncj)
        h, nca, _ = B.apply_attn(p_a, h, cfg, positions, cache=cag, mode=mode,
                                 cur_len=cur_len, chunk_q=chunk_q,
                                 chunk_k=chunk_k)
        if act_spec is not None:
            h = maybe_shard(h, act_spec)
        if mode == "train":
            return h, None
        ncg = jax.tree.map(lambda *xs: jnp.stack(xs), *ncg)
        return h, (ncg, nca)

    x, ys = jax.lax.scan(_maybe_remat(body, remat), x, (p_mg, c_mg, c_ag))
    if mode == "train":
        return x, None, jnp.zeros((), jnp.float32)
    new_c_m = jax.tree.map(lambda a: a.reshape((G * k,) + a.shape[2:]), ys[0])
    return x, (new_c_m, ys[1]), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            mode: str = "train", caches=None, cur_len=None,
            remat: bool = False, chunk_q: int = 2048, chunk_k: int = 2048,
            act_spec=None, p_bf16: bool = False, pages=None,
            paged_kernel: bool = False, return_prenorm: bool = False):
    """Returns (hidden (B,T,D), new_caches, aux_loss) — plus the
    pre-final-norm residual stream as a 4th element when
    ``return_prenorm=True`` (the serving engine preserves it so a
    depth-only hop can replay just the *new* layers instead of
    re-prefilling; see ``core.grow_cache.replay_grow_state``).

    ``pages``: (B, P) page table switching attention caches to the paged
    block-pool layout (decode mode, attention-cache families only; see
    ``serving.kv_pages``). ``paged_kernel`` reads the pools in place with
    the paged-attention kernel instead of gathering each layer's pool (the
    engine decides, ``kernels.ops.paged_kernel_ok``).

    ``act_spec``: optional PartitionSpec pinned onto the residual stream
    between blocks (e.g. P("data", "model", None) = Megatron-style sequence
    parallelism — divides saved scan-carry activations by the model-axis
    size; see EXPERIMENTS.md §Perf)."""
    offset = 0
    if mode == "decode":
        offset = cur_len - 1
        act_spec = None                       # T == 1: nothing to shard
    x, positions = embed(params, cfg, batch, offset=offset)
    if act_spec is not None:
        x = maybe_shard(x, act_spec)

    fam = cfg.family
    if fam == "ssm" and "mlstm" in params["layers"]:
        assert pages is None, "paged KV: attention-cache families only"
        x, new_caches, aux = _fwd_xlstm(params, x, cfg, mode=mode,
                                        caches=caches, remat=remat,
                                        act_spec=act_spec)
    elif fam == "hybrid":
        assert pages is None, "paged KV: attention-cache families only"
        x, new_caches, aux = _fwd_zamba(params, x, cfg, positions, mode=mode,
                                        caches=caches, cur_len=cur_len,
                                        remat=remat, chunk_q=chunk_q,
                                        chunk_k=chunk_k, act_spec=act_spec)
    else:
        x, new_caches, aux = _fwd_homogeneous(
            params, x, cfg, positions, mode=mode, caches=caches,
            cur_len=cur_len, remat=remat, chunk_q=chunk_q, chunk_k=chunk_k,
            act_spec=act_spec, p_bf16=p_bf16, pages=pages,
            paged_kernel=paged_kernel)
    prenorm = x
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if return_prenorm:
        return x, new_caches, aux, prenorm
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch_size: int, seq_len: int):
    """Zero-initialised per-layer caches + position counter."""
    dtype = _dtype(cfg)
    S = min(cfg.window, seq_len) if cfg.window else seq_len

    def attn_cache(lead):
        shape = tuple(lead) + (batch_size, S, cfg.n_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        caches = attn_cache((cfg.n_layers,))
    elif fam == "ssm":
        n_pairs = cfg.n_layers // 2
        di = cfg.ssm_expand * cfg.d_model
        H = cfg.n_heads
        dh = di // H
        m = {"conv": jnp.zeros((n_pairs, batch_size, cfg.conv_kernel - 1, di),
                               dtype),
             "S": jnp.zeros((n_pairs, batch_size, H, dh, dh), jnp.float32),
             "n": jnp.zeros((n_pairs, batch_size, H, dh), jnp.float32)}
        s = {kk: jnp.zeros((n_pairs, batch_size, cfg.d_model), jnp.float32)
             for kk in ("h", "c", "n")}
        s["m"] = jnp.full((n_pairs, batch_size, cfg.d_model), -1e30,
                          jnp.float32)
        caches = (m, s)
    elif fam == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        H, N = cfg.mamba_heads, cfg.ssm_state
        dh = di // H
        conv_ch = di + 2 * N
        G = cfg.n_layers // cfg.shared_attn_every
        m = {"conv": jnp.zeros((cfg.n_layers, batch_size, cfg.conv_kernel - 1,
                                conv_ch), dtype),
             "S": jnp.zeros((cfg.n_layers, batch_size, H, N, dh), jnp.float32),
             "n": jnp.zeros((cfg.n_layers, batch_size, H, N), jnp.float32)}
        caches = (m, attn_cache((G,)))
    else:
        raise ValueError(f"no decode path for family {fam}")
    return {"caches": caches, "pos": jnp.zeros((), jnp.int32)}


def decode_step(params, cfg: ModelConfig, state, batch: Dict[str, jax.Array],
                *, return_prenorm: bool = False,
                paged_kernel: bool = False) -> Tuple[jax.Array, Any]:
    """One-token decode: batch["tokens"]: (B, 1). Returns (logits (B,V), state).

    A ``state["pages"]`` entry switches attention caches to the paged
    layout; the table rides through unchanged (the host owns it), and
    ``paged_kernel`` has the layers read the pools in place (see
    :func:`forward`). With ``return_prenorm`` the result is (logits, state,
    prenorm (B,1,D))."""
    cur_len = state["pos"] + 1
    out = forward(params, cfg, batch, mode="decode", caches=state["caches"],
                  cur_len=cur_len, pages=state.get("pages"),
                  paged_kernel=paged_kernel, return_prenorm=return_prenorm)
    hidden, new_caches = out[0], out[1]
    logits = unembed(params, cfg, hidden[:, -1])
    new_state = {"caches": new_caches, "pos": cur_len}
    if "pages" in state:
        new_state["pages"] = state["pages"]
    if return_prenorm:
        return logits, new_state, out[3]
    return logits, new_state


def _pad_attn_caches(caches, cfg, S_target: int):
    """Grow attention K/V caches (seq axis = -3) to the decode budget."""
    def pad(leaf):
        S = leaf.shape[-3]
        if S >= S_target:
            return leaf
        widths = [(0, 0)] * leaf.ndim
        widths[-3] = (0, S_target - S)
        return jnp.pad(leaf, widths)

    def maybe(node):
        if isinstance(node, dict) and set(node) == {"k", "v"}:
            return {kk: pad(vv) for kk, vv in node.items()}
        return node

    return jax.tree.map(maybe, caches,
                        is_leaf=lambda n: isinstance(n, dict)
                        and set(n) == {"k", "v"})


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            max_len: Optional[int] = None,
            chunk_q: int = 2048, chunk_k: int = 2048, act_spec=None):
    """Full-sequence forward building decode caches. Returns (logits_last, state).

    ``max_len`` reserves cache space for subsequent decode steps (defaults to
    the prompt length — i.e. no room to decode — so callers serving requests
    must pass their generation budget).
    """
    T = (batch["tokens"] if "tokens" in batch else batch["frames"]).shape[1]
    hidden, caches, _ = forward(params, cfg, batch, mode="prefill",
                                chunk_q=chunk_q, chunk_k=chunk_k,
                                act_spec=act_spec)
    if max_len is not None and max_len > T:
        S_target = min(cfg.window, max_len) if cfg.window else max_len
        caches = _pad_attn_caches(caches, cfg, S_target)
    logits = unembed(params, cfg, hidden[:, -1])
    return logits, {"caches": caches, "pos": jnp.full((), T, jnp.int32)}
