"""KV-cache growth: migrate live decode state across an architecture hop.

The serving engine's live hop (``repro.serving``) swaps grown weights in
between two decode steps. In-flight sessions keep their per-slot K/V caches,
so the cache must be grown with the *same* operator as the weights or the
first post-hop attention read is garbage.

The rule falls out of the LiGO algebra: a cached key row is an activation
``k = x·Wk`` reshaped to ``(n_kv_heads, d_head)``. Growing ``Wk`` with the
out-expander ``E_k`` (``vec(Wk_big) = ... E_k``) means the grown activation
is ``k_big = E_k · k`` over the flattened ``(KV·dh)`` axis — the GrowthPlan
expander applied per cached position, for every position at once:

    K_big[l, b, s] = E_k @ K[l, b, s].reshape(KV1*dh1)

Depth blends average *layers*; a blended cache only equals the grown model's
own prefill when the blend is the identity, so the in-place rule is lossless
exactly for LEMON-style zero-pad operators (``operators.lemon_operator`` is
the bit-exactness oracle). Everything else — learned LiGO, depth growth,
SSM/hybrid recurrent state — takes the universal fallback: re-prefill the
session's token history under the grown weights (the engine keeps the
history for exactly this reason).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.ligo import _flatten, resolve_expander


class CacheGrowthError(RuntimeError):
    """A decode state cannot be grown in place — re-prefill the session."""


def can_grow_cache(cfg1: ModelConfig, cfg2: ModelConfig) -> bool:
    """Static eligibility: families whose whole decode state is one stacked
    attention K/V cache. SSM conv/state and hybrid caches have no linear
    growth rule (the recurrence mixes channels nonlinearly), and a changed
    attention window changes the cache budget — both re-prefill.

    The families need not *match*: a dense→MoE upcycle changes only the FFN,
    and the K/V cache never sees the FFN — each side just has to be an
    attention-cache family."""
    return (cfg1.family in ("dense", "moe", "vlm")
            and cfg2.family in ("dense", "moe", "vlm")
            and cfg1.window == cfg2.window)


def is_lossless_operator(ligo: Dict, cfg1: ModelConfig,
                         cfg2: ModelConfig) -> bool:
    """True iff ``ligo`` is a LEMON-style zero-pad operator, i.e. growing
    with it is bitwise function-preserving (see ``operators.lemon_operator``
    for why each condition is load-bearing).

    Checks concrete host values — call it outside jit (the hop controller
    does; it decides grow-vs-reprefill before launching any compiled work).
    """
    if (cfg1.d_model != cfg2.d_model or cfg1.d_head != cfg2.d_head
            or cfg1.n_layers != cfg2.n_layers):
        return False
    # Head gate: an unchanged head layout is always eligible — since PR 7's
    # Γ(I) = I lift, gamma_expand is exactly the identity there, so GQA
    # models take lossless d_ff/d_model/upcycle hops bitwise (no forced
    # re-prefill). Only when the layout *changes* does ``wo``'s grouped
    # in-expander average query heads within a kv group (the 1/G fan-in),
    # which breaks zero-pad exactness unless both sides are MHA.
    layout_same = (cfg1.n_heads == cfg2.n_heads
                   and cfg1.n_kv_heads == cfg2.n_kv_heads)
    if not layout_same and not (cfg1.n_heads == cfg1.n_kv_heads
                                and cfg2.n_heads == cfg2.n_kv_heads):
        return False
    for name, E in _flatten(ligo.get("width", {})).items():
        E = np.asarray(E)
        if E.ndim != 2:
            return False
        d2, d1 = E.shape
        if not np.array_equal(E[:d1], np.eye(d1)):
            return False
        if d2 > d1 and np.any(E[d1:]):
            return False
    for kind, leaves in ligo.get("depth", {}).items():
        for leaf, w in leaves.items():
            w = np.asarray(w)
            if w.shape[0] != w.shape[1] or not np.array_equal(
                    w, np.eye(w.shape[0])):
                return False
    return True


def kv_cache_expanders(ligo: Dict, cfg1: ModelConfig, cfg2: ModelConfig):
    """The (KV2·dh2, KV1·dh1) out-expanders for cached K and V — the same
    matrices the GrowthPlan applies to ``wk``/``wv`` columns."""
    width = ligo["width"]
    E_k = resolve_expander("k", width, cfg1, cfg2, "out")
    E_v = resolve_expander("v", width, cfg1, cfg2, "out")
    return E_k, E_v


def _expand_kv(C: jax.Array, E: jax.Array, cfg2: ModelConfig) -> jax.Array:
    """Apply a flat-kv-space expander per cached position, keeping the
    layout: a dense cache (L, B, S, KV1, dh1) → (L, B, S, KV2, dh2), a paged
    pool (L, n_blocks, bs, KV1·dh1) → (L, n_blocks, bs, KV2·dh2)."""
    lead = C.shape[:3]
    flat = C.reshape(lead + (-1,))
    out = jnp.einsum("...i,oi->...o", flat.astype(jnp.float32),
                     jnp.asarray(E, jnp.float32)).astype(C.dtype)
    if C.ndim == 4:
        return out
    return out.reshape(lead + (cfg2.n_kv_heads, cfg2.d_head))


def grow_attn_caches(caches: Dict[str, jax.Array], ligo: Dict,
                     cfg1: ModelConfig, cfg2: ModelConfig, *,
                     depth: str = "strict") -> Dict[str, jax.Array]:
    """Grow a stacked attention cache ``{"k","v"}: (L1,B,S,KV1,dh1)``, or
    paged pools ``(L1, n_blocks, bs, KV1·dh1)``, to the big architecture.
    ``depth="strict"`` (the serving default) refuses non-identity depth
    blends — a blended cache is an approximation, and the engine's
    re-prefill fallback is both exact and cheap at serving sequence
    lengths. ``depth="blend"`` applies the operator's ``wk``/``wv`` layer
    blends anyway (benchmarks, experiments)."""
    E_k, E_v = kv_cache_expanders(ligo, cfg1, cfg2)
    kind = cfg1.blocks[0]
    dwk = np.asarray(ligo["depth"][kind]["wk"])
    dwv = np.asarray(ligo["depth"][kind]["wv"])
    identity = (cfg1.n_layers == cfg2.n_layers
                and np.array_equal(dwk, np.eye(cfg1.n_layers))
                and np.array_equal(dwv, np.eye(cfg1.n_layers)))
    if not identity and depth != "blend":
        raise CacheGrowthError(
            "non-identity depth blend is not lossless for cached "
            "activations; re-prefill the session history instead")
    k = _expand_kv(caches["k"], E_k, cfg2)
    v = _expand_kv(caches["v"], E_v, cfg2)
    if not identity:
        k = jnp.einsum("kl,l...->k...", jnp.asarray(dwk, jnp.float32),
                       k.astype(jnp.float32)).astype(k.dtype)
        v = jnp.einsum("kl,l...->k...", jnp.asarray(dwv, jnp.float32),
                       v.astype(jnp.float32)).astype(v.dtype)
    return {"k": k, "v": v}


def grow_decode_state(state: Dict[str, Any], ligo: Dict, cfg1: ModelConfig,
                      cfg2: ModelConfig, *, depth: str = "strict",
                      mesh=None) -> Dict[str, Any]:
    """Grow a live decode state (``init_decode_state`` layout) in place of a
    re-prefill. Raises :class:`CacheGrowthError` whenever the in-place rule
    does not apply — callers treat that as "re-prefill this session".

    Paged states (a ``"pages"`` entry; ``serving.kv_pages``) grow
    *per-block*: the expander applies position-wise, so the block pool
    ``(L, n_blocks, bs, KV1·dh1)`` grows exactly like a dense row and the
    page table / allocator ride through untouched (block geometry is
    independent of the grown feature dims).

    With ``mesh``, the grown caches land carrying the ``state_pspecs``
    shardings for the *big* config, ready for the grown decode step (paged
    pools are replicated — ``state_pspecs`` describes dense rows)."""
    if not can_grow_cache(cfg1, cfg2):
        raise CacheGrowthError(
            f"family {cfg1.family!r} (window={cfg1.window}->{cfg2.window}): "
            "no in-place cache growth rule; re-prefill")
    new_caches = grow_attn_caches(state["caches"], ligo, cfg1, cfg2,
                                  depth=depth)
    new_state = {"caches": new_caches, "pos": state["pos"]}
    paged = "pages" in state
    if paged:
        new_state["pages"] = state["pages"]
    if mesh is not None:
        from repro.distributed.sharding import (P, named_shardings,
                                                state_pspecs)
        if paged:
            from jax.sharding import NamedSharding
            rep = NamedSharding(mesh, P())
            new_state = jax.device_put(new_state, jax.tree.map(
                lambda _: rep, new_state))
        else:
            ps = state_pspecs(new_state, cfg2,
                              model_size=mesh.shape.get("model", 1),
                              dp_size=mesh.shape.get("data", 1))
            new_state = jax.device_put(new_state, named_shardings(ps, mesh))
    return new_state


# ---------------------------------------------------------------------------
# Depth-replay fast path
# ---------------------------------------------------------------------------
def depth_replay_plan(ligo: Dict, cfg1: ModelConfig,
                      cfg2: ModelConfig) -> Optional[int]:
    """If the hop only *appends* layers — width untouched, every depth
    matrix carrying the old layers unchanged at the bottom of the grown
    stack (identity first-L1 rows; StackBERT's ``stack_pattern`` has this
    form) — the old layers' caches are already exact for the grown model,
    and only the new layers need K/V. Returns the preserved-prefix length
    (``cfg1.n_layers``), or None when the plan does not apply.

    Checks concrete host values — call outside jit (the hop controller
    decides the migration path before launching compiled work).
    """
    if not (cfg1.family in ("dense", "moe", "vlm")
            and cfg2.family == cfg1.family
            and cfg1.window == 0 and cfg2.window == 0
            and cfg2.n_layers > cfg1.n_layers
            and cfg1.blocks[0] == cfg2.blocks[0]):
        return None
    if (cfg1.d_model, cfg1.n_heads, cfg1.n_kv_heads, cfg1.d_head,
            cfg1.d_ff, cfg1.moe_d_ff) != (
            cfg2.d_model, cfg2.n_heads, cfg2.n_kv_heads, cfg2.d_head,
            cfg2.d_ff, cfg2.moe_d_ff):
        return None
    for name, E in _flatten(ligo.get("width", {})).items():
        E = np.asarray(E)
        if E.ndim != 2 or E.shape[0] != E.shape[1] or not np.array_equal(
                E, np.eye(E.shape[0])):
            return None
    L1, L2 = cfg1.n_layers, cfg2.n_layers
    for kind, leaves in ligo.get("depth", {}).items():
        for leaf, w in leaves.items():
            w = np.asarray(w)
            if w.shape != (L2, L1) or not np.array_equal(
                    w[:L1], np.eye(L1)):
                return None
    return L1


def replay_grow_state(state: Dict[str, Any], params2, cfg1: ModelConfig,
                      cfg2: ModelConfig, resid, *,
                      mesh=None) -> Dict[str, Any]:
    """Migrate a decode state across a depth-only hop by replaying *only
    the new layers* over the preserved residual stream.

    ``resid``: (slots, cap, D) — the pre-final-norm residual stream the
    engine recorded while serving the old model (positions beyond each
    slot's own length are garbage, exactly like cache padding: masked until
    overwritten). Because the hop preserves the old layers verbatim at the
    bottom of the stack, this stream *is* the input the appended layers see
    during the grown model's own prefill — so one forward through the
    ``L2-L1`` new layers rebuilds their caches, instead of ``L2`` layers of
    full re-prefill per session.

    Old-layer caches are reused as-is (width untouched ⇒ same (KV, dh)),
    for both the dense rows and the paged block pools.
    """
    from repro.models import blocks as B
    from repro.models.model import DTYPES
    n_old = cfg1.n_layers
    kind = cfg2.blocks[0]
    apply_block = B.apply_attn if kind == "attn" else B.apply_moe_block
    h = jnp.asarray(resid).astype(DTYPES[cfg2.dtype])
    cap = h.shape[1]
    positions = jnp.arange(cap)[None]
    p_stack = params2["layers"][kind]
    rows_k, rows_v = [], []
    for l in range(n_old, cfg2.n_layers):
        p_l = jax.tree.map(lambda a: a[l], p_stack)
        h, nc, _ = apply_block(p_l, h, cfg2, positions, mode="prefill")
        rows_k.append(nc["k"])
        rows_v.append(nc["v"])
    new_k = jnp.stack(rows_k)                   # (L_new, slots, cap, KV, dh)
    new_v = jnp.stack(rows_v)
    paged = "pages" in state
    if paged:
        table = state["pages"]                  # (slots, P)
        nb, bs = state["caches"]["k"].shape[1:3]
        feat = state["caches"]["k"].shape[3:]   # (KV·dh,)
        tgt = jnp.where(table >= 0, table, nb)  # unmapped → dropped

        def rows_to_pool(rows):
            L_new, slots = rows.shape[:2]
            blocks = rows.reshape((L_new, slots, cap // bs, bs) + feat)
            pool = jnp.zeros((L_new, nb, bs) + feat, rows.dtype)
            return pool.at[:, tgt].set(blocks)

        new_k, new_v = rows_to_pool(new_k), rows_to_pool(new_v)
    new_caches = {
        "k": jnp.concatenate([state["caches"]["k"],
                              new_k.astype(state["caches"]["k"].dtype)], 0),
        "v": jnp.concatenate([state["caches"]["v"],
                              new_v.astype(state["caches"]["v"].dtype)], 0)}
    new_state = {"caches": new_caches, "pos": state["pos"]}
    if paged:
        new_state["pages"] = state["pages"]
    if mesh is not None:
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import P as PS
        rep = NamedSharding(mesh, PS())
        new_state = jax.device_put(new_state,
                                   jax.tree.map(lambda _: rep, new_state))
    return new_state
