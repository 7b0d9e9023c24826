"""Optimizer-state growth: carry AdamW moments through a growth operator.

Growing a model mid-run (the trajectory regime, ``repro.trajectory``) must
not reset the optimizer: fresh zero moments throw away the curvature estimate
the small model spent its whole stage accumulating, and the first post-growth
steps spike the loss while AdamW re-warms (the failure mode LEMON, Wang et
al. 2023, attacks). Since every growth method here is a *linear* operator
``Θ_large = M Θ_small`` (LiGO Eq. 8 and all its classical special cases),
the moments map through the same operator with method-correct semantics:

- **first moment** ``m`` is an EMA of gradients; gradients of a linear
  reparametrisation pull back linearly, so ``m_large = M m_small`` — the
  operator applied as-is (``apply_ligo``).
- **second moment** ``v`` is an EMA of *squared* gradients; under the
  independent-gradient approximation ``E[(Σ cᵢ gᵢ)²] ≈ Σ cᵢ² E[gᵢ²]``, so
  ``v`` maps through the **elementwise-squared** operator
  (``apply_ligo(..., square=True)``): every resolved leaf expander and depth
  blend squared *after* resolution (resolve-then-square — for the GQA
  ``gamma`` expander the orders differ by the group-averaging factor).
  Squared factors are entrywise non-negative, so grown ``v`` stays ≥ 0 and
  ``sqrt(v)`` in the update is always defined.
- **schedule step** ``count`` is carried over unchanged, so bias correction
  and any count-keyed schedule continue instead of re-warming.
- the **weight-decay mask** is not state: ``adamw_update`` rebuilds it from
  the (grown) parameter tree every step, so vectors that became matrices (or
  vice versa) under the new architecture pick up the correct decay treatment
  automatically.

For selection-type operators (StackBERT / Net2Net one-hot factors) the
squared operator equals the operator itself on the out-role and the squared
normalised fan-in on the in-role — exactly LEMON's recipe; for learned LiGO
expanders it is the natural generalisation. ``method="random"`` has no
operator: start from ``adamw_init`` (the caller decides; see
``repro.core.grow``).
"""
from __future__ import annotations

from typing import Optional, Sequence

from repro.optim.adamw import AdamWState


def hop_uses_grouped_gamma(cfg1, cfg2) -> bool:
    """True when the (cfg1 → cfg2) hop's ``Γ(B_v)`` expander group-averages.

    ``gamma_expand`` is a pure block-repeat (identity mapping) when both
    ends are MHA (``n_kv_heads == n_heads``); under grouped heads it also
    column-averages over each source group (the ``/G1`` factor), and that
    averaging is what breaks squared-operator composition: for a group of
    coefficients ``cᵢ``, one composed hop squares the *sum* (``(Σcᵢ)²``)
    where per-hop squaring sums the *squares* (``Σcᵢ²``).
    """
    return (cfg1.n_kv_heads != cfg1.n_heads
            or cfg2.n_kv_heads != cfg2.n_heads)


def grow_adamw_state(state: AdamWState, op, cfg1, cfg2, *,
                     use_kernel: Optional[bool] = None,
                     mesh=None) -> AdamWState:
    """Map an AdamW state through a growth operator (see module docstring).

    ``state.m``/``state.v`` mirror the parameter tree, so both rides go
    through the same (memoised, optionally mesh-sharded) GrowthPlan the
    parameters used — moments are fp32 like the expanders, and their
    PartitionSpecs equal the parameter specs, so the sharded executor lands
    grown moments exactly where the train step wants them.
    """
    from repro.core.ligo import apply_ligo
    m = apply_ligo(op, state.m, cfg1, cfg2, use_kernel=use_kernel, mesh=mesh)
    v = apply_ligo(op, state.v, cfg1, cfg2, use_kernel=use_kernel, mesh=mesh,
                   square=True)
    return AdamWState(m=m, v=v, count=state.count)


def grow_adamw_state_chain(state: AdamWState, ops: Sequence, cfgs: Sequence,
                           *, use_kernel: Optional[bool] = None,
                           mesh=None) -> AdamWState:
    """Map an AdamW state through a *chain* of growth operators
    (``ops[i]: cfgs[i] → cfgs[i+1]``) — the skip-stage restart path.

    The GQA second-moments rule (ROADMAP): the **first moment** is linear,
    so it always rides the analytically composed operator — ONE fused
    A→…→Z apply, no intermediate trees. The **second moment** rides the
    squared operator, and squaring does not commute with composition when
    any hop's ``gamma`` expander group-averages (``Σcᵢ²`` per hop vs
    ``(Σcᵢ)²`` composed — see :func:`hop_uses_grouped_gamma`): in that case
    ``v`` is grown hop-by-hop through each squared operator, which is what a
    stage-by-stage run would have produced — so a skip-stage restart stays
    LEMON-exact. Pure-MHA chains keep the composed fast path for ``v`` too
    (one-hot factors square to themselves and dense MHA factors compose
    under the same independence approximation either way).
    """
    from repro.core.ligo import apply_ligo
    from repro.core.plan import compose_chain
    if len(ops) != len(cfgs) - 1:
        raise ValueError(f"{len(ops)} operators need {len(ops) + 1} "
                         f"configs, got {len(cfgs)}")
    if len(ops) == 1:
        return grow_adamw_state(state, ops[0], cfgs[0], cfgs[1],
                                use_kernel=use_kernel, mesh=mesh)
    composed = compose_chain(list(ops), list(cfgs))
    m = apply_ligo(composed, state.m, cfgs[0], cfgs[-1],
                   use_kernel=use_kernel, mesh=mesh)
    per_hop_v = any(hop_uses_grouped_gamma(a, b)
                    for a, b in zip(cfgs[:-1], cfgs[1:]))
    if per_hop_v:
        v = state.v
        for op, a, b in zip(ops, cfgs[:-1], cfgs[1:]):
            v = apply_ligo(op, v, a, b, use_kernel=use_kernel, mesh=mesh,
                           square=True)
    else:
        v = apply_ligo(composed, state.v, cfgs[0], cfgs[-1],
                       use_kernel=use_kernel, mesh=mesh, square=True)
    return AdamWState(m=m, v=v, count=state.count)
