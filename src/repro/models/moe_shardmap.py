"""Explicit-collective MoE (shard_map): the production dispatch path.

The dense scatter/gather MoE in :mod:`repro.models.moe` leaves partitioning
to GSPMD, which at 256-way meshes resolves the dispatch into TB-scale
partial-sum all-reduces of the capacity buffers (measured in §Perf — every
sharding-constraint variant made it worse). This module writes the collective
schedule explicitly instead:

  per (pod, data, model) chip:
    1. route + build the local capacity buffer (E, C_loc, D)    — local
    2. *virtual expert replication*: when E < data (mixtral: 8 < 16) each
       expert's capacity is split into ``rep = data/E`` virtual experts so
       the all-to-all still balances across the full data axis
    3. slice the capacity dim over ``model`` (inputs are model-replicated,
       so this is free dedup: each model shard handles C/m slots)
    4. all_to_all over ``data``: (E_v, C_vs, D) -> (E_v/dp, dp·C_vs, D)
       — the canonical MoE token exchange, on ICI neighbours
    5. dense expert FFN on the local expert(s)                  — local MXU
    6. reverse all_to_all; gather outputs back to token order   — local
    7. psum the (model-sliced) token outputs over ``model``

Capacity semantics are per-data-shard (standard local-dispatch MoE); with a
generous capacity factor it matches the dense path bit-for-bit (tested).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh


def _dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def moe_shardmap_available(cfg, mesh=None, batch_size=None) -> bool:
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or "data" not in mesh.axis_names:
        return False
    data = mesh.shape["data"]
    E = cfg.n_experts
    if not (E % data == 0 or data % E == 0):
        return False
    if batch_size is not None:
        dp = data
        for a in ("pod",):
            dp *= mesh.shape.get(a, 1)
        if batch_size % dp != 0:
            return False         # e.g. long_500k decode: batch 1 on dp 16
    return True


def apply_moe_shardmap(p, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, D) global. Returns (out, aux). See module docstring."""
    mesh = current_mesh()
    assert mesh is not None
    data_n = mesh.shape["data"]
    model_n = mesh.shape["model"]
    dp_axes = _dp_axes(mesh)
    E, k = cfg.n_experts, cfg.experts_top_k
    B, T, D = x.shape
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    N_loc = (B // dp_total) * T

    rep = max(1, data_n // E)              # virtual replicas per expert
    E_v = E * rep
    assert E_v % data_n == 0, (E, data_n)
    E_loc = E_v // data_n                  # virtual experts per data shard
    C_loc = int(math.ceil(k * N_loc * cfg.capacity_factor / E))
    C_loc = -(-C_loc // (rep * model_n)) * (rep * model_n)
    C_v = C_loc // rep                     # capacity per virtual expert
    C_vs = C_v // model_n                  # ... per model slice
    sharded_w = rep == 1                   # weights E/dp-sharded vs replicated
    has_w3 = "w3" in p

    def body(x_loc, router, w1, w2, *maybe_w3):
        w3 = maybe_w3[0] if maybe_w3 else None
        Bl = x_loc.shape[0]
        xf = x_loc.reshape(Bl * T, D)
        logits = xf.astype(jnp.float32) @ router            # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        frac = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32),
                        axis=0)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

        e_flat = top_e.reshape(-1)                          # (N·k,)
        w_flat = top_w.reshape(-1)
        # sort-based position-in-expert: O(N·k·log) and O(N·k) memory,
        # instead of the O(N·k·E) one-hot cumsum (268 MB/layer at qwen3
        # sizes — a dominant HBM stream in the dense path; §Perf)
        order = jnp.argsort(e_flat, stable=True)
        sorted_e = e_flat[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos_sorted = jnp.arange(e_flat.shape[0]) - starts[sorted_e]
        pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
        keep = pos < C_loc
        pos_c = jnp.minimum(pos, C_loc - 1)
        ve = e_flat * rep + pos_c // C_v                    # virtual expert
        pv = pos_c % C_v                                    # virtual slot

        # model-axis dedup: build ONLY this shard's capacity slice
        # [mi·C_vs, (mi+1)·C_vs) — 1/m of the buffer ever exists
        mi = jax.lax.axis_index("model")
        mine = (pv >= mi * C_vs) & (pv < (mi + 1) * C_vs) & keep
        x_rep = jnp.repeat(xf, k, axis=0) * mine[:, None].astype(x_loc.dtype)
        buf_sl = jnp.zeros((E_v, C_vs, D), x_loc.dtype).at[
            ve, jnp.clip(pv - mi * C_vs, 0, C_vs - 1)].add(x_rep)

        # MoE all-to-all over data: virtual experts to their owners
        a2a = jax.lax.all_to_all(buf_sl, "data", split_axis=0, concat_axis=1,
                                 tiled=True)        # (E_loc, dp·C_vs, D)
        if sharded_w:
            w1_l, w2_l = w1, w2                      # already (E/dp, ·, ·)
            w3_l = w3
        else:
            di = jax.lax.axis_index("data")
            real = di // rep                          # E_loc == 1 here
            w1_l = jax.lax.dynamic_slice_in_dim(w1, real, 1, axis=0)
            w2_l = jax.lax.dynamic_slice_in_dim(w2, real, 1, axis=0)
            w3_l = (jax.lax.dynamic_slice_in_dim(w3, real, 1, axis=0)
                    if w3 is not None else None)
        h = jnp.einsum("ecd,edf->ecf", a2a, w1_l)
        if w3_l is not None:
            h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", a2a, w3_l)
        else:
            h = jax.nn.gelu(h)
        y = jnp.einsum("ecf,efd->ecd", h, w2_l)      # (E_loc, dp·C_vs, D)
        y = jax.lax.all_to_all(y, "data", split_axis=1, concat_axis=0,
                               tiled=True)           # (E_v, C_vs, D)

        # combine: tokens whose slot lives on this model shard
        owner = pv // C_vs
        local = (owner == mi) & keep
        gathered = y[ve, pv % C_vs]                  # (N·k, D)
        gathered = gathered * (w_flat * local).astype(y.dtype)[:, None]
        out = jnp.sum(gathered.reshape(Bl * T, k, D), axis=1)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, dp_axes + ("model",))
        return out.reshape(Bl, T, D), aux

    batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None)
    espec = P("data", None, None) if sharded_w else P()
    in_specs = (batch_spec, P(), espec, espec) + ((espec,) if has_w3 else ())
    args = (x, p["router"], p["w1"], p["w2"]) + ((p["w3"],) if has_w3 else ())
    out, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=(batch_spec, P()),
                          check_vma=False)(*args)
    return out, aux
