"""Sharding rules: logical axes -> mesh axes, parameter specs, helpers.

Logical mesh axes are ``pod`` (cross-pod DP), ``data`` (in-pod DP/FSDP) and
``model`` (TP/EP). ``maybe_shard`` is a no-op outside a mesh context so the
same model code runs unsharded on one CPU device and sharded under pjit.

Convention: wherever a logical spec says ``"data"`` the physical spec uses
``("pod", "data")`` when a pod axis exists — i.e. the pod axis folds into
data-parallelism (batch + FSDP) by default. See DESIGN.md §5.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def current_mesh() -> Optional[Mesh]:
    """The ambient mesh installed by ``jax.set_mesh``, or None.

    Outside ``jit`` this is the concrete ``Mesh``: shardings built on it
    carry devices and memory kinds, which ``jax.jit(in_shardings=...)`` and
    ``device_put`` need. While tracing only the abstract mesh is visible,
    which is all ``with_sharding_constraint`` and ``shard_map`` need.
    """
    try:
        m = jax.sharding.get_mesh()
    except ValueError:                  # inside jit
        m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_attrs() -> dict:
    """Span attributes naming where the ambient mesh runs a program:
    ``mesh``, its axis sizes joined by "x" ("2x2"; "none" outside a mesh),
    and ``devices``, how many devices it spans."""
    mesh = current_mesh()
    if mesh is None:
        return {"mesh": "none", "devices": 1}
    return {"mesh": "x".join(str(n) for n in mesh.shape.values()),
            "devices": int(mesh.size)}


def physical_spec(spec: P, mesh) -> P:
    """Map logical 'data' to ('pod','data') when the mesh has a pod axis; drop
    axes the mesh doesn't have; drop shardings that don't divide evenly is left
    to XLA (we only translate names here)."""
    names = set(mesh.axis_names)
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        ax = entry if isinstance(entry, tuple) else (entry,)
        phys = []
        for a in ax:
            if a == "data" and "pod" in names:
                phys.extend(["pod", "data"])
            elif a in names:
                phys.append(a)
        out.append(tuple(phys) if len(phys) > 1 else (phys[0] if phys else None))
    return P(*out)


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint iff running under a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, physical_spec(spec, mesh))


def divisible_axes(dim: int, mesh) -> tuple:
    """Largest-first greedy subset of mesh axes whose size product divides
    ``dim`` — the axes a dimension of that extent can be sharded over without
    padding. Returns () when no axis (of size > 1) divides ``dim``.

    Used by the sharded growth path (kernels.ops / core.plan) to pick which
    dim of a leaf-group stack each shard_map shard owns."""
    chosen: list = []
    prod = 1
    for name, size in sorted(mesh.shape.items(), key=lambda kv: (-kv[1],
                                                                 str(kv[0]))):
        if size > 1 and dim % (prod * size) == 0:
            chosen.append(name)
            prod *= size
    return tuple(chosen)


# ---------------------------------------------------------------------------
# Parameter partition specs
# ---------------------------------------------------------------------------
# Logical rules, keyed by parameter-tree path suffixes. Layer-stacked leading
# dims (L, ...) are never sharded. TP shards: attention heads (qkvo), FFN
# hidden, expert hidden / expert count, vocab. FSDP shards the other matrix
# dim over 'data'.
def param_spec(path: str, ndim: int, shape=None, *, model_size: int = 16,
               dp_size: int = 16) -> P:
    leaf = path.split("/")[-1]
    stacked = path.startswith("layers/")
    lead = (None,) if stacked else ()
    sizes = {"model": model_size, "data": dp_size}

    def mk(*tail):
        full = lead + tail
        full = full + (None,) * (ndim - len(full))
        full = full[:ndim]
        if shape is not None:
            # drop any axis assignment the dimension doesn't divide
            full = tuple(a if (a is None or shape[i] % sizes[a] == 0) else None
                         for i, a in enumerate(full))
        return P(*full)

    if leaf in ("wq", "wk", "wv", "w1", "w3"):       # (D, out) — TP on out
        return mk("data", "model")
    if leaf in ("wo", "w2"):                          # (in, D) — TP on in
        return mk("model", "data")
    if leaf == "router":                              # (D, E)
        return mk("data", None)
    if leaf in ("tok", "head"):                       # (V, D) / (D, V|C)
        if leaf == "tok":
            return mk("model", "data")                # vocab TP
        return mk("data", "model")
    if leaf == "pos":                                 # (T, D)
        return mk(None, "data")
    if leaf in ("in_proj",):                          # mamba2 (D, big)
        return mk("data", "model")
    if leaf in ("out_proj", "down"):                  # (di, D)
        return mk("model", "data")
    if leaf in ("up",):                               # mLSTM up (D, 2di)
        return mk("data", "model")
    if leaf == "wqkv":                                # mLSTM (di, 3di)
        return mk("data", "model")
    if leaf == "gates":                               # mLSTM (di, 2H) — tiny out
        return mk("data", None)
    if leaf == "r":                                   # sLSTM recurrent (D, 4D)
        return mk("data", "model")
    if leaf == "w":                                   # sLSTM input (D, 4D)
        return mk("data", "model")
    # MoE expert stacks (E, D, F) / (E, F, D): EP on E when divisible.
    if stacked and ndim >= 3 and leaf in ("w1e", "w2e", "w3e"):
        return mk("model", None, None)
    # vectors (norm scales, biases, conv kernels, gate params): replicated
    return P(*((None,) * ndim))


def params_pspecs(params: Any, *, model_size: int = 16,
                  dp_size: int = 16, moe_layout: str = "fsdp") -> Any:
    """Build a pytree of PartitionSpec mirroring a parameter pytree.

    ``moe_layout``:
      - "fsdp" (baseline): expert tensors (L, E, in, out) FSDP-shard their
        *contraction* dim over data — which GSPMD resolves with enormous
        partial-sum all-reduces of the (E, C, ·) buffers (measured: 2.3 TB
        per step on mixtral train_4k; see §Perf).
      - "tp_ep": never shard a contraction dim. E over model (EP) when
        divisible, else hidden over model (TP); the *layer-stack* dim carries
        the FSDP/data sharding so optimiser state still scales with dp.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        # MoE expert tensors live under .../moe/{w1,w2,w3} with ndim 4
        if "/moe/" in "/" + pstr + "/" and leaf.ndim == 4:
            L, E = leaf.shape[0], leaf.shape[1]
            if moe_layout == "shardmap":
                # explicit-collective MoE (models/moe_shardmap.py): experts
                # over *data* (EP) when they divide; otherwise (virtual
                # replication path) weights enter shard_map replicated, so
                # *storage* is FSDP+TP sharded and GSPMD gathers one layer's
                # slice per scan step (2.8GB transient, not 90GB resident).
                if E % dp_size == 0:
                    specs.append(P(None, "data", None, None))
                else:
                    specs.append(P(None, None, "data", "model")
                                 if pstr.endswith(("w1", "w3"))
                                 else P(None, None, "model", "data"))
            elif moe_layout == "tp_ep":
                lspec = "data" if L % dp_size == 0 else None
                if E % model_size == 0:
                    specs.append(P(lspec, "model", None, None))
                else:
                    specs.append(P(lspec, None, None, "model")
                                 if pstr.endswith(("w1", "w3"))
                                 else P(lspec, None, "model", None))
            elif E % model_size == 0:
                specs.append(P(None, "model", "data", None)
                             if leaf.shape[2] % dp_size == 0
                             else P(None, "model", None, None))
            else:
                specs.append(P(None, None, "data", "model")
                             if pstr.endswith(("w1", "w3"))
                             else P(None, None, "model", "data"))
        else:
            specs.append(param_spec(pstr, leaf.ndim, leaf.shape,
                                    model_size=model_size, dp_size=dp_size))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(pspecs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, physical_spec(s, mesh)),
                        pspecs, is_leaf=lambda x: isinstance(x, P))


# Activation specs (logical)
ACT_BTD = P("data", None, None)         # (B, T, D)
ACT_BTH = P("data", None, "model")      # (B, T, H·dh) / heads sharded
BATCH = P("data")


def batch_specs(batch: Any, *, dp_size: int = 0) -> Any:
    """Shard every batch leaf's leading (batch) dim over data (if divisible)."""
    def spec(leaf):
        if dp_size and leaf.ndim and leaf.shape[0] % max(dp_size, 1) != 0:
            return P(*((None,) * leaf.ndim))
        return P(*(("data",) + (None,) * (leaf.ndim - 1)))
    return jax.tree.map(spec, batch)


# ---------------------------------------------------------------------------
# Decode-state partition specs (KV caches / SSM states)
# ---------------------------------------------------------------------------
def state_pspecs(state: Any, cfg, *, model_size: int = 16,
                 dp_size: int = 16) -> Any:
    """Sharding for decode state: batch over data; heads over model when they
    divide, otherwise the cache *sequence* dim over model (sequence-parallel
    decode — partial-softmax combine is GSPMD-inserted)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    specs = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        nd = leaf.ndim
        if name in ("k", "v") and nd >= 4:
            # (..., B, S, KV, dh)
            kv = leaf.shape[-2]
            if kv % model_size == 0:
                tail = ["seq_slot_none", "model", None]
            else:
                tail = ["model_seq", "kv_none", None]
            spec = [None] * (nd - 4) + ["batch_slot"] + tail
        elif name == "S" and nd >= 4:          # (..., B, H, dk, dv)
            h = leaf.shape[-3]
            spec = [None] * (nd - 4) + ["batch_slot",
                                        "model" if h % model_size == 0 else None,
                                        None, None]
        elif name == "n" and nd >= 4:          # GLA normaliser (..., B, H, dk)
            h = leaf.shape[-2]
            spec = [None] * (nd - 3) + ["batch_slot",
                                        "model" if h % model_size == 0 else None,
                                        None]
        elif name == "conv" and nd >= 3:       # (..., B, K-1, C)
            spec = [None] * (nd - 3) + ["batch_slot", None, None]
        elif name in ("h", "c", "n", "m") and nd == 3:  # sLSTM (L, B, D)
            d = leaf.shape[-1]
            spec = [None, "batch_slot",
                    "model" if d % model_size == 0 else None]
        else:                                   # pos counter etc.
            specs.append(P(*((None,) * nd)))
            continue
        # resolve markers
        out = []
        for s in spec:
            if s == "batch_slot":
                bdim = leaf.shape[len(out)]
                out.append("data" if bdim % dp_size == 0 else None)
            elif s == "seq_slot_none" or s == "kv_none":
                out.append(None)
            elif s == "model_seq":
                out.append("model")
            else:
                out.append(s)
        specs.append(P(*out))
    return jax.tree_util.tree_unflatten(treedef, specs)
