"""GrowthPlan: a compiled, fused growth engine for ``apply_ligo``.

The legacy ``apply_ligo`` walks the parameter tree leaf by leaf, re-resolving
every expander expression (``gamma`` block-repeats, ``seg`` block-diagonals)
per leaf per call and emitting per-leaf einsums. That is the hot path of the
whole reproduction: it runs — and is differentiated through — on every one of
the ~100 LiGO SGD steps, and again for the final materialisation.

A :class:`GrowthPlan` is compiled **once** per ``(cfg1, cfg2, tree shape)``
and fixes, ahead of time:

1. the set of *distinct* ``(expander expression, role)`` pairs — resolved
   exactly once per apply (shared across all leaves) instead of per leaf;
2. a grouping of parameter leaves by ``(module family, shape, in/out-expander
   pair)`` — each group executes as a single stacked/batched contraction
   instead of per-leaf einsums;
3. a static, FLOP-cost-model choice of contraction order per group
   (expand-then-blend vs blend-then-expand), and whether the group is
   eligible for the fused Pallas blend-expand path
   (:func:`repro.kernels.ligo_blend_expand_grouped_vjp`, a ``jax.custom_vjp``
   over the *whole group*) — on TPU the widened ``(L1, D2o, D2i)`` stack then
   never exists in HBM, forward or backward.

Fused-path coverage and backward dataflow
-----------------------------------------
Kernel eligibility (``LeafGroup.kernel_ok``) is decided by
:func:`repro.kernels.fused_eligible` and is *universal* in shape: any stacked
``(L1, a, b)`` or MoE ``(L1, E, a, b)`` leaf with an in-expander qualifies —
the group dim G and expert dim E fold into the kernel grid (one launch per
group, not per leaf) and non-128-aligned dims run on cdiv grids with
in-kernel zero-masked ragged tiles, so vocab-projection-sized and odd-head
shapes are no longer rejected. The only exclusions are degenerate dims and
groups whose blocks and accumulators would overflow the kernels' VMEM limit
(see :func:`repro.kernels.fused_vmem_bytes`).
:meth:`GrowthPlan.kernel_groups` counts the groups that take the fused
route; the others run the einsum contractions.

The backward pass — the LiGO phase's hot loop, differentiated on every SGD
step — is a *single* fused Pallas pass over the ``dP`` tiles
(:func:`repro.kernels.ligo_blend_expand_bwd_fused`) that emits all three
cotangents together: ``dW = Bᵀ(Σ_k w[k,l] dP[k])`` accumulated per-tile,
and ``dB``/``dw`` accumulated in *small-space* VMEM scratch with tiny
``(n_b, I, A)`` / ``(n_b, N, L2, L1)`` partials reduced outside — so
``dP``/``W``/``B`` each move between HBM and VMEM exactly once per launch
and no widened ``(L1, D2o, ·)`` intermediate exists in either direction.

``plan_for(cfg1, cfg2, small)`` memoises plans; ``plan.executor()`` memoises
one jitted callable per plan, so eager callers (``grow()``'s final
materialisation, benchmarks, serving-time elastic growth) pay a single
dispatch instead of hundreds.

Sharded growth
--------------
``apply``/``executor`` take an optional ``mesh``: the plan then carries
shardings end-to-end. Per-leaf-group ``PartitionSpec``s are derived from
:func:`repro.distributed.sharding.params_pspecs` (the same rules the trained
model's weights live under, so grown leaves land exactly where the training
step wants them), the LiGO operator tree — expanders ``E_in``/``E_out`` and
depth blends — is replicated, and ``executor(mesh=...)`` emits ``jax.jit``
with ``in_shardings``/``out_shardings`` built from those specs. Inside the
traced apply each group's stacked contraction gets a sharding constraint, and
the fused Pallas path runs the grouped custom_vjp **per shard** under
``shard_map`` (:func:`repro.kernels.ligo_blend_expand_grouped_sharded`): the
kernel only contracts the blend (L1) and expansion (A) dims, so sharding the
trailing output dim (or the group dim) needs no cross-device traffic. A group
takes that route when the kernels take the shape one shard's launch sees
(:meth:`GrowthPlan.fused_routes`), else the einsum contractions; every
executor build counts its groups by route (``ligo.groups_fused``,
``ligo.groups_einsum`` in the obs registry). Callers
that sit under an ambient mesh (``jax.set_mesh`` — the train/serve
drivers) pick this up automatically through ``apply_ligo``.

Operator composition
--------------------
Multi-stage trajectories (``repro.trajectory``) chain hops small→mid→…→large.
:func:`compose_ligo` / :func:`compose_chain` fold successive operators into
one ``cfg_A→cfg_C`` LiGO tree analytically — Kronecker width factors as
matrix products, depth patterns as chained blends — so any stage-A→stage-C
growth (``serve --grow-to a,b,c``, skip-stage restarts) runs as a *single*
fused GrowthPlan without ever materialising the intermediate models. This
exactness is for the *linear* map (parameters, first moments): the squared
(second-moment) operator of a composition is NOT the composition of the
squared hops for dense or GQA-``gamma`` factors (elementwise ``(B·A)²``
carries cross terms that ``B²·A²`` does not) — grow AdamW ``v`` per hop
when that distinction matters (see the ROADMAP open item).

The legacy path survives as ``apply_ligo(..., engine="legacy")`` — the
correctness oracle every plan output is tested against.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from collections import Counter
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core import spec as S
from repro.core.ligo import (_flatten, _kind_counts, _unflatten,
                             resolve_expander)
from repro.distributed.sharding import (named_shardings, params_pspecs,
                                        physical_spec)
from repro import obs
from repro.kernels.ops import (LAUNCH_COUNTS, fused_eligible,
                               ligo_blend_expand_grouped_sharded,
                               ligo_blend_expand_grouped_vjp, shard_split)

# Trace-time instrumentation (tests assert expanders are resolved once per
# apply-trace, not once per leaf, and that train_ligo never re-traces).
RESOLVE_COUNTS: Counter = Counter()
# Leaf groups by route, counted once per executor build: on the fused
# kernels (under ``shard_map`` when the executor has a mesh), and on the
# einsum contractions. Their sum over a build is the plan's group count.
GROUPS_FUSED = obs.counter("ligo.groups_fused")
GROUPS_EINSUM = obs.counter("ligo.groups_einsum")

ExprRef = Tuple[Any, str]          # (hashable expr key, role) — plan.exprs key


def _expr_key(expr) -> Any:
    """Canonical hashable key for a spec expander expression."""
    if expr is None or isinstance(expr, str):
        return expr
    kind = expr[0]
    if kind == "gamma":
        return ("gamma", _expr_key(expr[1]))
    if kind == "seg":
        return ("seg", tuple((_expr_key(sub), n1, n2)
                             for (sub, n1, n2) in expr[1]))
    raise ValueError(expr)


def _expr_dims(expr, cfg1: ModelConfig, cfg2: ModelConfig) -> Tuple[int, int]:
    """Static (d2, d1) shape of a resolved expander expression."""
    if isinstance(expr, str):
        return S.width_dims(cfg2)[expr], S.width_dims(cfg1)[expr]
    if expr[0] == "gamma":
        return (cfg2.n_heads * cfg2.d_head, cfg1.n_heads * cfg1.d_head)
    if expr[0] == "seg":
        return (sum(n2 for (_, _, n2) in expr[1]),
                sum(n1 for (_, n1, _) in expr[1]))
    raise ValueError(expr)


@dataclass(frozen=True)
class LeafGroup:
    """A batch of same-shaped leaves sharing one (in, out) expander pair."""
    kind: str                      # layer-stack kind; "" for top-level params
    stacked: bool                  # leading L1 layer dim present
    paths: Tuple[str, ...]
    shape: Tuple[int, ...]         # per-leaf shape (incl. L1 when stacked)
    in_ref: Optional[ExprRef]
    out_ref: Optional[ExprRef]
    vec: bool                      # per-layer vector leaf (out-expander only)
    order: Tuple[str, ...]         # op sequence drawn from {in, out, blend}
    kernel_ok: bool                # fused Pallas custom_vjp path eligible
    # Family-changing hops (dense→MoE upcycling): where the grown leaves
    # land. Defaults mean "same kind / same paths" (every same-family plan).
    out_kind: str = ""             # target stack kind when it differs
    out_paths: Tuple[str, ...] = ()  # target leaf paths when renamed
    bcast: int = 0                 # expert-replication count (0 = none)
    # what fused_eligible weighed (L2, grown in-dim i, itemsize), for the
    # per-shard eligibility under a mesh; None off the kernel route
    fuse_dims: Optional[Tuple[int, int, int]] = None

    @property
    def dst_kind(self) -> str:
        return self.out_kind or self.kind

    @property
    def dst_paths(self) -> Tuple[str, ...]:
        return self.out_paths or self.paths


def _best_order(ops_present, L1: int, L2: int, extra: int, a: int, b: int,
                i: int, j: int) -> Tuple[str, ...]:
    """Min-FLOP ordering of the (commuting) expand/blend contractions.

    The three ops are bilinear maps applied to independent axes, so any
    ordering is semantically equal; cost is not. Exhaustive search over the
    ≤ 3! arrangements with a running (layers, a, b) dim state.
    """
    from itertools import permutations
    best, best_cost = None, None
    for perm in dict.fromkeys(permutations(ops_present)):
        l, ca, cb = L1, a, b
        cost = 0
        for op in perm:
            if op == "in":
                cost += extra * l * i * ca * cb
                ca = i
            elif op == "out":
                cost += extra * l * ca * cb * j
                cb = j
            else:  # blend
                cost += extra * L2 * L1 * ca * cb
                l = L2
        if best_cost is None or cost < best_cost:
            best, best_cost = perm, cost
    return best if best is not None else ()


def _plan_group(kind: str, stacked: bool, paths, shape, in_e, out_e,
                vec: bool, L2: int, cfg1, cfg2, itemsize: int) -> LeafGroup:
    """Choose contraction order + kernel eligibility from static shapes."""
    in_ref = None if in_e is None else (_expr_key(in_e), "in")
    out_ref = None if out_e is None else (_expr_key(out_e), "out")
    blended = stacked
    L1 = shape[0] if stacked else 1
    if vec:
        n = shape[-1]
        j = _expr_dims(out_e, cfg1, cfg2)[0] if out_e is not None else n
        ops_present = tuple(op for op, c in (("out", out_e is not None),
                                             ("blend", blended)) if c)
        order = _best_order(ops_present, L1, L2, 1, 1, n, 1, j)
        return LeafGroup(kind, stacked, tuple(paths), tuple(shape), None,
                         out_ref, True, order, False)

    a, b = shape[-2], shape[-1]
    extra = 1
    for d in shape[(1 if stacked else 0):-2]:
        extra *= d
    i = _expr_dims(in_e, cfg1, cfg2)[0] if in_e is not None else a
    j = _expr_dims(out_e, cfg1, cfg2)[0] if out_e is not None else b
    ops_present = tuple(op for op, c in (("in", in_e is not None),
                                         ("out", out_e is not None),
                                         ("blend", blended)) if c)
    order = _best_order(ops_present, L1, L2, extra, a, b, i, j)
    # Fused Pallas eligibility: stacked (L1, a, b) or MoE (L1, E, a, b) with
    # an in-expander — G/E fold into the grid, ragged dims are masked
    # in-kernel, so only the kernels' VMEM limit can reject a real shape.
    fusable = blended and in_e is not None and len(shape) in (3, 4)
    kernel_ok = fusable and fused_eligible(L1, L2, extra, i, a, b,
                                           G=len(paths), itemsize=itemsize)
    return LeafGroup(kind, stacked, tuple(paths), tuple(shape), in_ref,
                     out_ref, False, order, kernel_ok,
                     fuse_dims=(L2, i, itemsize) if fusable else None)


class GrowthPlan:
    """Static execution plan for growing Θ_small → Θ_large.

    Built once per ``(cfg1, cfg2, parameter-tree signature)`` via
    :func:`plan_for`; ``apply`` is a pure, differentiable function of
    ``(ligo_params, small_params)`` with identical semantics to the legacy
    ``apply_ligo`` walk.
    """

    def __init__(self, cfg1: ModelConfig, cfg2: ModelConfig,
                 groups: Tuple[LeafGroup, ...],
                 exprs: Dict[ExprRef, Any],
                 created: Optional[Dict[str, Dict[str, Tuple]]] = None):
        self.cfg1, self.cfg2 = cfg1, cfg2
        self.groups = groups
        self.exprs = exprs
        # Target-only leaves with no source (family hops): kind → {path:
        # (full stacked shape, dtype)}, materialised as zeros by ``apply``
        # (zeros are the function-preserving router init AND the right
        # created value for both AdamW moment maps).
        self.created = created or {}
        self._executors: Dict[Any, Any] = {}
        self._spec_cache: Dict[Tuple[int, int], Any] = {}
        self._route_reported = False

    def kernel_groups(self) -> Tuple[int, int]:
        """(groups that take the fused Pallas route, all groups)."""
        return sum(g.kernel_ok for g in self.groups), len(self.groups)

    @staticmethod
    def shard_shape(g: LeafGroup, mesh: Optional[Mesh]) -> Tuple[int, int]:
        """(G, Bd): the group count and trailing dim of the stack one
        shard's kernel launch sees under ``mesh`` (the whole stack's
        without one, or when neither dim divides over it)."""
        G, b = len(g.paths), g.shape[-1]
        if mesh is None:
            return G, b
        axes_b, axes_g = shard_split(G, b, mesh)
        for ax in axes_b:
            b //= mesh.shape[ax]
        for ax in axes_g:
            G //= mesh.shape[ax]
        return G, b

    def fused_routes(self, use_kernel: bool,
                     mesh: Optional[Mesh] = None) -> Tuple[bool, ...]:
        """Per group: does an apply run it on the fused kernels? Under a
        mesh a group is eligible at the shape one shard's launch sees;
        the others take the einsum contractions."""
        out = []
        for g in self.groups:
            ok = use_kernel and g.fuse_dims is not None
            if ok:
                L2, i, itemsize = g.fuse_dims
                G, b = self.shard_shape(g, mesh)
                extra = g.shape[1] if len(g.shape) == 4 else 1
                ok = fused_eligible(g.shape[0], L2, extra, i, g.shape[-2], b,
                                    G=G, itemsize=itemsize)
            out.append(bool(ok))
        return tuple(out)

    def _report_route(self, fused: Tuple[bool, ...]) -> None:
        """Say once per plan which groups a kernel-enabled apply runs on the
        einsum contractions instead of the fused kernels."""
        if self._route_reported:
            return
        self._route_reported = True
        einsum = [f"{g.kind or 'top'}/{g.paths[0]}{list(g.shape)}"
                  + (f"x{len(g.paths)}" if len(g.paths) > 1 else "")
                  for g, f in zip(self.groups, fused) if not f]
        print(f"[plan] {self.cfg1.name} -> {self.cfg2.name}: {sum(fused)}/"
              f"{len(fused)} groups on the fused kernels; einsum route: "
              f"{', '.join(einsum)}", file=sys.stderr)

    # -- resolution cache (one resolve per distinct (expr, role) per apply) --
    def _expander_table(self, width) -> Dict[ExprRef, jax.Array]:
        table = {}
        for ref_, expr in self.exprs.items():
            RESOLVE_COUNTS["resolve"] += 1
            table[ref_] = resolve_expander(expr, width, self.cfg1, self.cfg2,
                                           ref_[1])
        return table

    # -- group execution ----------------------------------------------------
    # Expansions execute as single large GEMMs (leading group/layer dims
    # folded into the GEMM M dim) rather than per-leaf batched dot_generals —
    # XLA:CPU runs batched dots well below plain-GEMM throughput, and the
    # fold is free for the out-side (row-major last dim) / one transpose for
    # the in-side.
    @staticmethod
    def _expand_out(X: jax.Array, E: jax.Array) -> jax.Array:
        """(..., b) · Eᵀ → (..., j) as one (prod(...), b)×(b, j) GEMM."""
        s = X.shape
        out = X.reshape(-1, s[-1]) @ E.astype(X.dtype).T
        return out.reshape(s[:-1] + (E.shape[0],))

    @staticmethod
    def _expand_in(X: jax.Array, E: jax.Array) -> jax.Array:
        """E · (..., a, b) → (..., i, b) as one (i, a)×(a, prod(·)) GEMM."""
        a = X.shape[-2]
        Xm = jnp.moveaxis(X, -2, 0)                      # (a, ..., b)
        s = Xm.shape
        out = E.astype(X.dtype) @ Xm.reshape(a, -1)
        return jnp.moveaxis(out.reshape((E.shape[0],) + s[1:]), 0, -2)

    @staticmethod
    def _run_group(g: LeafGroup, X: jax.Array, E_in, E_out, w_g):
        """X: (G, ...) stacked leaves; w_g: (G, L2, L1) blends or None.

        Executes the group's static min-FLOP op sequence; the blend op is
        skipped when the operator tree carries no depth blends for this kind.
        """
        for op in g.order:
            if op == "in":
                X = GrowthPlan._expand_in(X, E_in)
            elif op == "out":
                X = GrowthPlan._expand_out(X, E_out)
            elif w_g is not None:
                X = jnp.einsum("gkl,gl...->gk...", w_g.astype(X.dtype), X)
        return X

    @staticmethod
    def _run_group_fused(g: LeafGroup, X, E_in, E_out, w_g,
                         mesh: Optional[Mesh] = None):
        """Fused Pallas path: blend + left-expand for the *whole group* via
        the grouped custom_vjp kernel — the G leaves and any MoE expert dim E
        fold into the kernel grid, so the group is ONE launch forward and ONE
        fused multi-cotangent launch backward (the widened (L1, D2o, ·) stack
        never hits HBM in either direction). The right expansion is a plain
        (already-optimal) matmul on the kernel's output.

        With a ``mesh`` the custom_vjp runs per shard under ``shard_map``
        (trailing-dim or group-dim sharding; see
        :func:`repro.kernels.ligo_blend_expand_grouped_sharded`) — still one
        launch per group per device."""
        moe = X.ndim == 5                      # (G, L1, E, a, b) expert stack
        Xg = X if moe else X[:, :, None]       # insert E=1 for plain leaves
        P = ligo_blend_expand_grouped_sharded(w_g, E_in.astype(X.dtype), Xg,
                                              mesh, use_kernel=True)
        if not moe:
            P = P[:, :, 0]
        if E_out is not None:
            P = GrowthPlan._expand_out(P, E_out)
        return P

    def apply(self, ligo, small, *, use_kernel: Optional[bool] = None,
              mesh: Optional[Mesh] = None, square: bool = False,
              constrain_groups: bool = True):
        """Θ_large = M(Θ_small) — plan-driven, differentiable in both args.

        With a ``mesh``, each group's stacked contraction carries the
        ``params_pspecs``-derived sharding constraint and the fused path runs
        under ``shard_map`` — see :meth:`executor` for the fully-sharded
        (``in_shardings``/``out_shardings``) entry point.
        ``constrain_groups=False`` drops the per-group constraints; only
        correct when the caller pins the outputs itself (``executor(mesh=)``
        does, via ``out_shardings`` — re-constraining every stacked group
        mid-program forced an extra resharding per group, the bulk of the
        8-device apply regression).

        ``square=True`` squares every resolved expander and depth blend
        elementwise after resolution — the AdamW second-moment map (the
        growth operator is linear in its factors, so the fused kernel and
        every contraction order work unchanged on the squared factors).
        """
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        fused = self.fused_routes(use_kernel, mesh)
        if use_kernel:
            self._report_route(fused)
        group_sh = (self._group_shardings(mesh)
                    if mesh is not None and constrain_groups else None)
        width = ligo["width"]
        depth = ligo.get("depth", {})
        table = self._expander_table(width)
        if square:
            table = {ref_: E * E for ref_, E in table.items()}

        flat_stacks = {kind: _flatten(stack)
                       for kind, stack in small["layers"].items()}
        flat_top = _flatten({k: v for k, v in small.items() if k != "layers"})

        grown_stacks: Dict[str, Dict[str, jax.Array]] = {
            g.dst_kind: {} for g in self.groups if g.dst_kind}
        for kind in self.created:
            grown_stacks.setdefault(kind, {})
        grown_top: Dict[str, jax.Array] = {}

        for gidx, g in enumerate(self.groups):
            src = flat_stacks[g.kind] if g.kind else flat_top
            leaves = [src[p] for p in g.paths]
            blend_tree = depth.get(g.kind) if (g.stacked and g.kind) else None
            w_g = (jnp.stack([blend_tree[p] for p in g.paths])
                   if blend_tree is not None else None)
            if square and w_g is not None:
                w_g = w_g * w_g
            E_in = table[g.in_ref] if g.in_ref is not None else None
            E_out = table[g.out_ref] if g.out_ref is not None else None
            X = leaves[0][None] if len(leaves) == 1 else jnp.stack(leaves)
            if fused[gidx] and w_g is not None:
                out = self._run_group_fused(g, X, E_in, E_out, w_g, mesh=mesh)
            else:
                if use_kernel:
                    LAUNCH_COUNTS.inc("einsum")
                out = self._run_group(g, X, E_in, E_out, w_g)
            if g.bcast:
                # Expert replication: (G, L2, a, b) → (G, L2, E, a, b).
                # Coefficient-1 copies square to themselves, so the same
                # broadcast serves params, m, and the squared v map.
                out = jnp.broadcast_to(
                    out[:, :, None],
                    out.shape[:2] + (g.bcast,) + out.shape[2:])
            if group_sh is not None:
                out = jax.lax.with_sharding_constraint(out, group_sh[gidx])
            dst = grown_stacks[g.dst_kind] if g.kind else grown_top
            for gi, p in enumerate(g.dst_paths):
                dst[p] = out[gi]

        for kind, leaves_c in self.created.items():
            for path, (shape, dt) in leaves_c.items():
                grown_stacks[kind][path] = jnp.zeros(shape, dtype=dt)

        out_tree: Dict[str, Any] = {"layers": {
            kind: _unflatten(grown) for kind, grown in grown_stacks.items()}}
        out_tree.update(_unflatten(grown_top))
        return out_tree

    def executor(self, *, use_kernel: Optional[bool] = None,
                 mesh: Optional[Mesh] = None, square: bool = False):
        """A cached jitted ``(ligo, small) -> big`` for this plan.

        With a ``mesh`` the program is pjit-compiled with
        ``in_shardings``/``out_shardings`` from :meth:`shardings`: the LiGO
        operator tree replicated, small/large leaves sharded exactly like
        their model weights (``params_pspecs``) — so growth of 8B+ targets
        runs distributed and the grown tree lands ready for the sharded
        train step with no resharding. ``square=True`` compiles the
        elementwise-squared (second-moment) variant — AdamW ``v`` trees
        share the parameter shardings, so the same in/out specs apply.
        """
        key = (use_kernel, mesh, square)
        if key not in self._executors:
            fused = self.fused_routes(jax.default_backend() == "tpu"
                                      if use_kernel is None else use_kernel,
                                      mesh)
            GROUPS_FUSED.inc(sum(fused))
            GROUPS_EINSUM.inc(len(fused) - sum(fused))
            if mesh is None:
                fn = functools.partial(GrowthPlan.apply, self,
                                       use_kernel=use_kernel, square=square)
                self._executors[key] = jax.jit(fn)
            else:
                # out_shardings already pin every grown leaf; the per-group
                # with_sharding_constraint would only force an extra
                # resharding per stacked group inside the program.
                fn = functools.partial(GrowthPlan.apply, self,
                                       use_kernel=use_kernel, mesh=mesh,
                                       square=square, constrain_groups=False)
                ligo_sh, small_sh, big_sh = self.shardings(mesh)
                self._executors[key] = jax.jit(
                    fn, in_shardings=(ligo_sh, small_sh),
                    out_shardings=big_sh)
        return self._executors[key]

    # -- sharding (PartitionSpecs per leaf/group, derived once per mesh) ----
    def _out_shape(self, g: LeafGroup, L2: int) -> Tuple[int, ...]:
        """Static per-leaf output shape of a group (big-model side)."""
        def d2(ref, dflt):
            if ref is None:
                return dflt
            return _expr_dims(self.exprs[ref], self.cfg1, self.cfg2)[0]
        if g.vec:
            j = d2(g.out_ref, g.shape[-1])
            return (L2, j) if g.stacked else (j,)
        i = d2(g.in_ref, g.shape[-2])
        j = d2(g.out_ref, g.shape[-1])
        mid = g.shape[(1 if g.stacked else 0):-2]
        if g.stacked and g.bcast:
            return (L2, g.bcast) + mid + (i, j)   # expert-replicated stack
        return ((L2,) + mid + (i, j)) if g.stacked else (mid + (i, j))

    def _abstract_trees(self):
        """(small, big) parameter trees of ShapeDtypeStructs rebuilt from the
        plan's group metadata — structurally identical to the trees ``apply``
        consumes and produces."""
        c2 = _kind_counts(self.cfg2)
        small: Dict[str, Dict[str, Any]] = {}
        big: Dict[str, Dict[str, Any]] = {}
        for g in self.groups:
            out_shape = self._out_shape(g, c2.get(g.dst_kind, 0))
            for p in g.paths:
                small.setdefault(g.kind, {})[p] = jax.ShapeDtypeStruct(
                    g.shape, jnp.float32)
            for p in g.dst_paths:
                big.setdefault(g.dst_kind, {})[p] = jax.ShapeDtypeStruct(
                    out_shape, jnp.float32)
        for kind, leaves_c in self.created.items():
            for p, (shape, dt) in leaves_c.items():
                big.setdefault(kind, {})[p] = jax.ShapeDtypeStruct(
                    tuple(shape), dt)

        def tree(flat: Dict[str, Dict[str, Any]]):
            t: Dict[str, Any] = {"layers": {
                kind: _unflatten(d) for kind, d in flat.items() if kind}}
            t.update(_unflatten(flat.get("", {})))
            return t
        return tree(small), tree(big)

    def pspecs(self, mesh: Mesh):
        """(small, big) logical ``PartitionSpec`` trees for this plan under
        ``mesh`` — the exact specs :func:`params_pspecs` prescribes for the
        small/large model weights. The LiGO operator tree carries no entry
        here: expanders and depth blends enter replicated — every shard of a
        leaf contraction consumes the expanders whole (the fused route's
        G-dim fallback may re-slice the stacked blend internally, see
        :func:`repro.kernels.ligo_blend_expand_grouped_sharded`)."""
        model_sz = mesh.shape.get("model", 1)
        dp_sz = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        key = (model_sz, dp_sz)
        if key not in self._spec_cache:
            small_t, big_t = self._abstract_trees()
            self._spec_cache[key] = (
                params_pspecs(small_t, model_size=model_sz, dp_size=dp_sz),
                params_pspecs(big_t, model_size=model_sz, dp_size=dp_sz))
        return self._spec_cache[key]

    def shardings(self, mesh: Mesh):
        """(ligo, small, big) ``NamedSharding`` trees for ``executor(mesh=)``.
        The ligo entry is a single replicated sharding used as a pytree
        prefix for the whole operator tree."""
        small_ps, big_ps = self.pspecs(mesh)
        return (NamedSharding(mesh, PartitionSpec()),
                named_shardings(small_ps, mesh),
                named_shardings(big_ps, mesh))

    def _group_shardings(self, mesh: Mesh):
        """Per-group ``NamedSharding`` for the stacked (G, ...) group output:
        a leading None for the group dim + the group's first leaf's
        params_pspecs entry (all leaves in a group share one shape)."""
        _, big_ps = self.pspecs(mesh)
        flat = {kind: _flatten(stack)
                for kind, stack in big_ps["layers"].items()}
        flat[""] = _flatten({k: v for k, v in big_ps.items()
                             if k != "layers"})
        return [NamedSharding(mesh, physical_spec(
            PartitionSpec(None, *flat[g.dst_kind][g.dst_paths[0]]), mesh))
            for g in self.groups]


# ---------------------------------------------------------------------------
# Plan construction (memoised on config pair + tree signature)
# ---------------------------------------------------------------------------
def _tree_signature(small) -> Tuple:
    """(path, shape, itemsize) of every leaf — the itemsize sizes the fused
    kernels' VMEM blocks."""
    def leaf(p, v):
        return (p, tuple(v.shape), jnp.dtype(v.dtype).itemsize)
    layers = tuple(sorted(
        (kind, tuple(sorted(leaf(p, v)
                            for p, v in _flatten(stack).items())))
        for kind, stack in small["layers"].items()))
    top = tuple(sorted(leaf(p, v) for p, v in _flatten(
        {k: v for k, v in small.items() if k != "layers"}).items()))
    return (layers, top)


@functools.lru_cache(maxsize=128)
def _build_plan(cfg1: ModelConfig, cfg2: ModelConfig, sig) -> GrowthPlan:
    layers_sig, top_sig = sig
    c2 = _kind_counts(cfg2)
    groups = []
    exprs: Dict[ExprRef, Any] = {}
    hop = S.family_hop(cfg1, cfg2)
    kmap = hop["kind_map"] if hop else {}
    renames = hop["renames"] if hop else {}
    bcast_map = hop["broadcast"] if hop else {}

    def register(expr, role: str) -> Optional[ExprRef]:
        if expr is None:
            return None
        ref_ = (_expr_key(expr), role)
        exprs.setdefault(ref_, expr)
        return ref_

    for kind, leaves in layers_sig:
        lspec = S.layer_spec(kind, cfg1, cfg2)
        stacked = kind != "shared_attn"
        tgt_kind = kmap.get(kind, kind)
        L2 = c2.get(tgt_kind, 0)
        buckets: Dict[Tuple, list] = {}
        for path, shape, isz in leaves:
            in_e, out_e = lspec[path]
            vec = len(shape) == (2 if stacked else 1)
            dst = renames.get(path, path)
            bc = bcast_map.get(dst, 0)
            key = (shape, _expr_key(in_e) if not vec else None,
                   _expr_key(out_e), vec, bc)
            buckets.setdefault(key, []).append(
                (path, dst, in_e, out_e, isz))
        for (shape, _ik, _ok, vec, bc), members in sorted(buckets.items(),
                                                          key=str):
            paths = tuple(m[0] for m in members)
            dsts = tuple(m[1] for m in members)
            in_e, out_e = members[0][2], members[0][3]
            g = _plan_group(kind, stacked, paths, shape,
                            None if vec else in_e, out_e, vec, L2, cfg1, cfg2,
                            max(m[4] for m in members))
            if hop is not None:
                g = dataclasses.replace(
                    g, out_kind=tgt_kind if tgt_kind != kind else "",
                    out_paths=dsts if dsts != paths else (), bcast=bc)
            if not vec:
                register(in_e, "in")
            register(out_e, "out")
            groups.append(g)

    tspec = S.top_spec()
    buckets = {}
    for path, shape, isz in top_sig:
        in_e, out_e = tspec[path]
        vec = len(shape) == 1
        key = (shape, _expr_key(in_e) if not vec else None,
               _expr_key(out_e), vec)
        buckets.setdefault(key, []).append((path, in_e, out_e, isz))
    for (shape, _ik, _ok, vec), members in sorted(buckets.items(), key=str):
        paths = tuple(m[0] for m in members)
        in_e, out_e = members[0][1], members[0][2]
        g = _plan_group("", False, paths, shape, None if vec else in_e,
                        out_e, vec, 0, cfg1, cfg2,
                        max(m[3] for m in members))
        if not vec:
            register(in_e, "in")
        register(out_e, "out")
        groups.append(g)

    created: Dict[str, Dict[str, Tuple]] = {}
    if hop is not None:
        for kind, leaves_c in hop.get("created", {}).items():
            created[kind] = {
                path: ((c2[kind],) + tuple(shape), dt)
                for path, (shape, dt) in leaves_c.items()}
    return GrowthPlan(cfg1, cfg2, tuple(groups), exprs, created)


def plan_for(cfg1: ModelConfig, cfg2: ModelConfig, small) -> GrowthPlan:
    """The (memoised) GrowthPlan for growing ``small`` from cfg1 to cfg2."""
    return _build_plan(cfg1, cfg2, _tree_signature(small))


def place_operator(ligo: Dict, mesh: Mesh) -> Dict:
    """Replicate an operator tree onto ``mesh`` ahead of the apply.

    ``executor(mesh=)`` declares the LiGO tree replicated via
    ``in_shardings``; feeding it host (or single-device) arrays makes every
    apply pay the full broadcast on its own critical path. Hot paths — the
    serving hop, the sharded-apply benchmark — call this once and reuse the
    device-resident tree across applies (and across the executor cache's
    ``square`` variants, which share the same replicated placement)."""
    sh = NamedSharding(mesh, PartitionSpec())
    return jax.device_put(ligo, jax.tree.map(lambda _: sh, ligo))


# ---------------------------------------------------------------------------
# Operator composition: stage-A→B ∘ stage-B→C as a single A→C operator
# ---------------------------------------------------------------------------
# A growth trajectory (small→mid→…→large, repro.trajectory) produces one
# LiGO-parameter tree per hop. Because every hop is *linear* in Θ and the
# depth blend acts on the layer axis while the width expanders act on the
# matrix axes, successive hops compose analytically:
#
#   P₃ = w_B·(E_B P₂ F_Bᵀ)  with  P₂ = w_A·(E_A W F_Aᵀ)
#      = (w_B w_A)·((E_B E_A) W (F_B F_A)ᵀ)
#
# i.e. the composed operator's Kronecker width factors are plain matrix
# products of the per-hop factors and its depth patterns are chained
# ``(L₃×L₂)·(L₂×L₁)`` blends. The tying registry commutes with this:
# ``Γ₂₃(B)·Γ₁₂(A) = Γ₁₃(B·A)`` (the G₂ row-repeats of the inner hop cancel
# the /G₂ column-averaging of the outer hop) and block-diagonal ``seg``
# expressions compose block-by-block. So ``compose_ligo`` needs only the
# *named* width matrices — never the resolved per-leaf expanders — and the
# result is an ordinary LiGO tree for ``(cfg1, cfg3)``: feed it to
# ``plan_for(cfg1, cfg3, small)`` and any stage-A→stage-C growth runs as a
# SINGLE fused GrowthPlan without materialising the intermediate model
# (``serve --grow-to a,b,c``, skip-stage trajectory restarts).
def _chain_matmul(B, A):
    """``B @ A`` for two operator factors, exactly rounded.

    Concrete factors multiply on the host in float64 and round once to the
    storage dtype — the composed operator then carries no accumulation error
    of its own, keeping composed-vs-sequential apply differences down to the
    two applies' own rounding (≤1e-6 relative at trajectory scales). Traced
    factors (composing under jit) fall back to a device matmul.
    """
    import numpy as np
    if isinstance(B, jax.core.Tracer) or isinstance(A, jax.core.Tracer):
        return B @ A
    out = np.asarray(B, np.float64) @ np.asarray(A, np.float64)
    return jnp.asarray(out.astype(jnp.promote_types(B.dtype, A.dtype)))


def compose_ligo(op_a: Dict, op_b: Dict, cfg1: ModelConfig,
                 cfg2: ModelConfig, cfg3: ModelConfig) -> Dict:
    """Compose LiGO operators ``op_a: cfg1→cfg2`` and ``op_b: cfg2→cfg3``
    into the equivalent single-hop ``cfg1→cfg3`` operator.

    Untied in-expanders (``<name>__in``, e.g. Net2Net's normalised fan-in
    copies) compose role-wise: the in-role product is taken over each hop's
    *in-resolved* matrix, falling back to the tied matrix when a hop has no
    override.
    """
    S.check_growable(cfg1, cfg2)
    S.check_growable(cfg2, cfg3)
    wa, wb = op_a["width"], op_b["width"]
    width: Dict[str, jax.Array] = {}
    for name in sorted(n for n in wb if not n.endswith("__in")):
        if name not in wa:
            raise KeyError(f"width expander {name!r} missing from the "
                           f"first-hop operator")
        A, B = wa[name], wb[name]
        if A.shape[0] != B.shape[1]:
            raise ValueError(f"{name}: hop dims do not chain "
                             f"({A.shape} then {B.shape})")
        width[name] = _chain_matmul(B, A)
        if f"{name}__in" in wa or f"{name}__in" in wb:
            Ai = wa.get(f"{name}__in", A)
            Bi = wb.get(f"{name}__in", B)
            width[f"{name}__in"] = _chain_matmul(Bi, Ai)
    depth: Dict[str, Any] = {}
    da, db = op_a.get("depth", {}), op_b.get("depth", {})
    c1, c2_, c3 = (_kind_counts(cfg1), _kind_counts(cfg2),
                   _kind_counts(cfg3))
    for kind in sorted(set(da) | set(db)):
        ta, tb = da.get(kind), db.get(kind)
        if ta is None or tb is None:
            # one hop carries no blend for this kind — an implicit identity,
            # only sound when that hop does not change the layer count
            lo, hi = ((c1, c2_) if ta is None else (c2_, c3))
            if lo.get(kind, 0) != hi.get(kind, 0):
                raise ValueError(
                    f"hop without a depth blend for kind {kind!r} changes "
                    f"its layer count {lo.get(kind, 0)} -> "
                    f"{hi.get(kind, 0)} — cannot compose through an "
                    f"implicit identity")
            depth[kind] = dict(tb if ta is None else ta)
            continue
        if sorted(ta) != sorted(tb):
            raise ValueError(f"depth leaf sets differ for kind {kind!r}")
        depth[kind] = {leaf: _chain_matmul(tb[leaf], ta[leaf])
                       for leaf in ta}
    return {"width": width, "depth": depth}


def compose_chain(ops, cfgs) -> Dict:
    """Fold a whole trajectory's operators ``[op₁₂, op₂₃, …]`` over the
    config chain ``[cfg₁, cfg₂, …, cfg_N]`` into one ``cfg₁→cfg_N``
    operator (a single-entry chain passes through unchanged)."""
    if len(ops) != len(cfgs) - 1:
        raise ValueError(f"{len(ops)} operators need {len(ops) + 1} configs, "
                         f"got {len(cfgs)}")
    if not ops:
        raise ValueError("empty operator chain")
    out = ops[0]
    for i in range(1, len(ops)):
        out = compose_ligo(out, ops[i], cfgs[0], cfgs[i], cfgs[i + 1])
    return out
