"""LiGO: the learned Linear Growth Operator (paper Eq. 8).

``vec(Θ_large) = L_depth · R_width · vec(Θ_small)`` with

- width: per-tensor ``Ω = E_in · W · E_outᵀ`` where the expanders are resolved
  from a small set of learnable matrices (B_emb, B_q, B_k, B_v, B_fc1, ...)
  through the tying registry in :mod:`repro.core.spec` — the Kronecker
  factorisation ``R_l = A_l ⊗ B_l`` of §3.2.2, applied as the equivalent
  two-sided matrix product (Eq. 7) so the full ``D₂²×D₁²`` operator is never
  materialised;
- depth: per-module blend ``Ω'_{l₂} = Σ_j w[l₂,j] Ω_j`` (the ``w ⊗ I``
  factorisation of L_depth), one learnable ``w ∈ R^{L₂×L₁}`` per module family
  exactly as in Alg. 1.

``apply_ligo`` is a pure, differentiable function of (ligo_params, Θ_small) —
the LiGO training phase backpropagates the task loss through it into the
expanders. Untied in-expanders (needed to express Net2Net's normalised
duplication exactly, App. A Eq. 12) are supported by storing an override under
``"<name>__in"``.

Two execution engines: ``engine="plan"`` (default) compiles the growth once
per (cfg1, cfg2, tree) into a :class:`repro.core.plan.GrowthPlan` — cached
expander resolution, leaves batched by (family, shape, expander pair),
min-FLOP contraction order, fused Pallas blend-expand on TPU;
``engine="legacy"`` is the original per-leaf walk below, kept as the
correctness oracle (tests assert plan == legacy for every operator).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import spec as S

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Expander resolution
# ---------------------------------------------------------------------------
def gamma_expand(Bv: jax.Array, cfg1: ModelConfig, cfg2: ModelConfig
                 ) -> jax.Array:
    """Γ(B_v): kv-head-space expander → query-head-space expander.

    Block-repeats each kv-group block over its group's query heads; identity
    mapping for MHA (KV == H), which recovers the paper's ``A^O = B_vᵀ``.
    """
    KV1, KV2 = cfg1.n_kv_heads, cfg2.n_kv_heads
    H1, H2 = cfg1.n_heads, cfg2.n_heads
    dh1, dh2 = cfg1.d_head, cfg2.d_head
    if KV1 == H1 and KV2 == H2:
        return Bv
    G1, G2 = H1 // KV1, H2 // KV2
    B = Bv.reshape(KV2, dh2, KV1, dh1)
    if KV1 == KV2 and H1 == H2 and dh1 == dh2:
        # Unchanged head layout (d_model/d_ff-only hop on a GQA model):
        # lift per group position — query head (g, j) maps through B_v's
        # (g → g') block to query head (g', j). Γ(I) = I, so lossless
        # operators stay bitwise function-preserving on GQA (the dup+avg
        # lift below rewrites wo even for the identity). Exactly the MHA
        # behaviour when G == 1.
        T = jnp.einsum("adbe,jk->ajdbke", B, jnp.eye(G1, dtype=B.dtype))
        return T.reshape(H2 * dh2, H1 * dh1)
    B = jnp.repeat(B, G2, axis=0)                  # query heads of large model
    B = jnp.repeat(B, G1, axis=2) / G1             # average over small groups
    return B.reshape(H2 * dh2, H1 * dh1)


def resolve_expander(expr, width: Params, cfg1: ModelConfig,
                     cfg2: ModelConfig, role: str) -> Optional[jax.Array]:
    """Materialise an expander expression to a (d2, d1) matrix (or None)."""
    if expr is None:
        return None
    if isinstance(expr, str):
        if role == "in" and f"{expr}__in" in width:
            return width[f"{expr}__in"]
        return width[expr]
    kind = expr[0]
    if kind == "gamma":
        return gamma_expand(
            resolve_expander(expr[1], width, cfg1, cfg2, role), cfg1, cfg2)
    if kind == "seg":
        blocks = []
        for (sub, n1, n2) in expr[1]:
            if sub is None:
                assert n1 == n2
                blocks.append(jnp.eye(n1))
            else:
                m = resolve_expander(sub, width, cfg1, cfg2, role)
                assert m.shape == (n2, n1), (sub, m.shape, (n2, n1))
                blocks.append(m)
        return jax.scipy.linalg.block_diag(*blocks)
    raise ValueError(expr)


def expand_leaf(W: jax.Array, E_in: Optional[jax.Array],
                E_out: Optional[jax.Array]) -> jax.Array:
    """Ω = E_in · W · E_outᵀ in the x@W convention; broadcast leading dims."""
    out = W
    if E_in is not None:
        out = jnp.einsum("ia,...ab->...ib", E_in.astype(W.dtype), out)
    if E_out is not None:
        out = jnp.einsum("...ab,jb->...aj", out, E_out.astype(W.dtype))
    return out


def expand_vector(v: jax.Array, E_out: Optional[jax.Array]) -> jax.Array:
    if E_out is None:
        return v
    return jnp.einsum("ja,...a->...j", E_out.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Parameter-tree walking
# ---------------------------------------------------------------------------
def _flatten(d: Params, prefix: str = "") -> Dict[str, jax.Array]:
    out = {}
    for k, v in d.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten(v, p))
        else:
            out[p] = v
    return out


def _unflatten(flat: Dict[str, jax.Array]) -> Params:
    out: Params = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def _kind_counts(cfg: ModelConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for k in cfg.blocks:
        counts[k] = counts.get(k, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# LiGO params: init
# ---------------------------------------------------------------------------
def _expand_init(key, d2: int, d1: int, noise: float) -> jax.Array:
    """[I; random-row-copies] + noise — a Net2Net-flavoured starting point.

    For shrinking spaces (d2 < d1, e.g. an MHA→GQA head merge) the start
    point is the truncated identity [I 0] — keep the first d2 features.
    """
    k1, k2 = jax.random.split(key)
    eye = jnp.eye(d2, d1)
    if d2 > d1:
        src = jax.random.randint(k1, (d2 - d1,), 0, d1)
        eye = jnp.concatenate([jnp.eye(d1), jax.nn.one_hot(src, d1)], axis=0)
    return eye + noise * jax.random.normal(k2, (d2, d1))


def stack_pattern(L2: int, L1: int) -> jnp.ndarray:
    """StackBERT: layer l₂ copies layer l₂ mod L₁ (paper Eq. 1)."""
    return jax.nn.one_hot(jnp.arange(L2) % L1, L1)


def interp_pattern(L2: int, L1: int) -> jnp.ndarray:
    """Interpolation: layer l₂ copies layer ⌊l₂·L₁/L₂⌋ (paper Eq. 1)."""
    return jax.nn.one_hot(jnp.arange(L2) * L1 // L2, L1)


def init_ligo_params(key, cfg1: ModelConfig, cfg2: ModelConfig, *,
                     depth_init: str = "stack", noise: float = 0.01) -> Params:
    """Learnable LiGO parameters: width expanders + per-module depth blends."""
    S.check_growable(cfg1, cfg2)
    d1s, d2s = S.width_dims(cfg1), S.width_dims(cfg2)
    keys = jax.random.split(key, len(d2s) + 1)
    width = {}
    for i, name in enumerate(sorted(d2s)):
        width[name] = _expand_init(keys[i], d2s[name], d1s[name], noise)
    pattern = stack_pattern if depth_init == "stack" else interp_pattern
    depth: Dict[str, Any] = {}
    c1, c2 = _kind_counts(cfg1), _kind_counts(cfg2)
    hop = S.family_hop(cfg1, cfg2)
    kmap = hop["kind_map"] if hop else {}
    for kind in c1:
        # Depth blends are keyed by SOURCE kind; on a family-changing hop
        # the target layer count lives under the mapped kind.
        L1k, L2k = c1[kind], c2[kmap.get(kind, kind)]
        depth[kind] = {leaf: pattern(L2k, L1k)
                       for leaf in S.layer_spec(kind, cfg1, cfg2)}
    return {"width": width, "depth": depth}


def count_ligo_params(ligo: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(ligo))


# ---------------------------------------------------------------------------
# Apply: Θ_large = M(Θ_small)
# ---------------------------------------------------------------------------
def apply_ligo(ligo: Params, small: Params, cfg1: ModelConfig,
               cfg2: ModelConfig, *, engine: str = "plan",
               use_kernel: Optional[bool] = None, mesh=None,
               square: bool = False) -> Params:
    """Grow a small model's parameter tree into the large architecture.

    ``engine="plan"`` (default) routes through the compiled
    :class:`repro.core.plan.GrowthPlan` — expanders resolved once per call,
    leaves batched by (family, shape, expander pair), fused Pallas
    blend-expand on TPU. ``engine="legacy"`` keeps the original per-leaf
    einsum walk as the correctness oracle. ``use_kernel`` forces/disables the
    fused Pallas path (plan engine only; default: auto — TPU yes, CPU no).

    ``mesh`` (plan engine only) runs the growth sharded: the executor is
    pjit-compiled with ``params_pspecs``-derived in/out shardings (expanders
    replicated, leaf stacks sharded like their model weights) and the fused
    path runs per shard under ``shard_map``. Default: the ambient mesh
    installed by ``jax.set_mesh`` when one exists — the train/serve
    drivers grow distributed without passing anything.

    ``square=True`` applies the *elementwise-squared* operator: every
    resolved leaf expander and depth blend is squared after resolution
    (resolve-then-square — for ``gamma``'s group averaging the two orders
    differ). This is the AdamW second-moment map: if ``p_large = Σ cᵢ pᵢ``
    then under the independent-gradient approximation ``v_large = Σ cᵢ² vᵢ``
    — see :func:`repro.optim.grow_adamw_state`.
    """
    if engine in ("plan", "auto"):
        from repro.core.plan import plan_for
        if mesh is None:
            from repro.distributed.sharding import current_mesh
            mesh = current_mesh()
        plan = plan_for(cfg1, cfg2, small)
        return plan.executor(use_kernel=use_kernel, mesh=mesh,
                             square=square)(ligo, small)
    if engine != "legacy":
        raise ValueError(f"unknown growth engine {engine!r}")
    width = ligo["width"]
    top = S.top_spec()
    out_layers: Params = {}
    hop = S.family_hop(cfg1, cfg2)
    kmap = hop["kind_map"] if hop else {}
    renames = hop["renames"] if hop else {}
    bcast = hop["broadcast"] if hop else {}
    c2 = _kind_counts(cfg2)

    def _sq(E):
        return None if E is None else E * E

    for kind, stack in small["layers"].items():
        lspec = S.layer_spec(kind, cfg1, cfg2)
        flat = _flatten(stack)
        grown: Dict[str, jax.Array] = {}
        stacked = kind != "shared_attn"
        for path, W in flat.items():
            in_e, out_e = lspec[path]
            E_in = resolve_expander(in_e, width, cfg1, cfg2, "in")
            E_out = resolve_expander(out_e, width, cfg1, cfg2, "out")
            if square:
                E_in, E_out = _sq(E_in), _sq(E_out)
            vec = W.ndim == (2 if stacked else 1)
            wide = (expand_vector(W, E_out) if vec
                    else expand_leaf(W, E_in, E_out))
            if stacked and kind in ligo["depth"]:
                blend = ligo["depth"][kind][path]
                if square:
                    blend = blend * blend
                wide = jnp.einsum("kl,l...->k...", blend.astype(wide.dtype),
                                  wide)
            dst = renames.get(path, path)
            if dst in bcast:
                # Expert replication (coefficient-1 copies): (L2, a, b) →
                # (L2, E, a, b). 1² == 1, so the broadcast is equally the
                # squared operator — correct for AdamW v as well as params/m.
                E = bcast[dst]
                wide = jnp.broadcast_to(wide[:, None],
                                        wide.shape[:1] + (E,) + wide.shape[1:])
            grown[dst] = wide
        tgt_kind = kmap.get(kind, kind)
        for cpath, (shape, dt) in (hop or {}).get("created", {}).get(
                tgt_kind, {}).items():
            grown[cpath] = jnp.zeros((c2[tgt_kind],) + tuple(shape), dtype=dt)
        out_layers[tgt_kind] = _unflatten(grown)

    out: Params = {"layers": out_layers}
    flat_top = _flatten({k: v for k, v in small.items() if k != "layers"})
    grown_top: Dict[str, jax.Array] = {}
    for path, W in flat_top.items():
        in_e, out_e = top[path]
        E_in = resolve_expander(in_e, width, cfg1, cfg2, "in")
        E_out = resolve_expander(out_e, width, cfg1, cfg2, "out")
        if square:
            E_in, E_out = _sq(E_in), _sq(E_out)
        if W.ndim == 1:
            grown_top[path] = expand_vector(W, E_out)
        else:
            grown_top[path] = expand_leaf(W, E_in, E_out)
    out.update(_unflatten(grown_top))
    return out
