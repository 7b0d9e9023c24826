"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True`` — the
kernel body runs step-by-step in Python against the same BlockSpec tiling, so
correctness (incl. the grid/accumulator logic) is what's validated; on TPU the
same calls compile to Mosaic. ``backend()`` picks automatically.

``ligo_blend_expand_grouped_vjp`` is the differentiable entry point used by
the GrowthPlan engine (:mod:`repro.core.plan`): a ``jax.custom_vjp`` around
the fused depth-blend + width-expand primitive over a whole leaf group
(G leaves × E experts folded into the kernel grid — one launch per group).
Its backward pass is :func:`repro.kernels.ligo_expand_bwd.
ligo_blend_expand_bwd_fused`, a single fused pass over the ``dP`` tiles that
emits all three cotangents (dW, dB, dw) with small-space scratch accumulation
— the widened ``(L1, D2o, ...)`` stack is never materialised in either
direction, and ``dP``/``W``/``B`` each stream from HBM exactly once. On CPU
(``use_kernel=False``) both directions fall back to the einsum formulation in
:mod:`repro.kernels.ref`, which accumulates in float32 via
``preferred_element_type`` while streaming operands at param dtype (no
HBM-doubling upcast for bf16 trees).

``LAUNCH_COUNTS`` is trace-time instrumentation: tests assert the plan engine
issues one fused launch per leaf group (not per leaf) by tracing an apply and
counting. It is a locked :class:`repro.obs.CounterGroup` ("kernels.launches"
in the obs registry), so the hop's background grow thread can trace
concurrently with the decode loop without losing increments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ligo_expand import (fused_eligible, fused_vmem_bytes,
                                       ligo_blend_expand as _blend_expand,
                                       ligo_blend_expand_grouped as
                                       _blend_expand_grouped)
from repro.kernels.ligo_expand_bwd import (ligo_blend_expand_bwd_fused as
                                           _bwd_fused)
from repro.kernels.paged_attention import page_fits
from repro.kernels.paged_attention import paged_attention as _paged_attention
from repro.obs import CounterGroup, counter_group

# Trace-time kernel launch counter ({"fwd": n, "bwd": n, "paged_attn": n}),
# thread-safe (locked), registered in the obs registry as "kernels.launches".
LAUNCH_COUNTS: CounterGroup = counter_group("kernels.launches")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def ligo_blend_expand(w, B, W, **kw):
    """P[l2] = B @ (Σ_l w[l2,l] W[l]) — fused depth-blend + left expansion."""
    return _blend_expand(w, B, W, interpret=_interpret(), **kw)


def ligo_blend_expand_grouped(w, B, W, **kw):
    """Grouped fused blend-expand: (G, L1, E, A, Bd) stacks, one launch."""
    return _blend_expand_grouped(w, B, W, interpret=_interpret(), **kw)


def ligo_blend_expand_bwd_fused(w, B, W, dP, **kw):
    """Fused (dw, dB, dW) cotangents — one pass over the dP tiles."""
    return _bwd_fused(w, B, W, dP, interpret=_interpret(), **kw)


def ligo_grow(w, B, A, W, **kw):
    """Full fused growth Ω[l2] = B (Σ_l w[l2,l] W_l) Aᵀ.

    The left expansion + blend runs in the Pallas kernel; the right expansion
    is a plain (already-optimal) matmul on the kernel's output.
    """
    P = ligo_blend_expand(w, B, W, **kw)
    return jnp.einsum("kib,jb->kij", P, A)


# ---------------------------------------------------------------------------
# Differentiable fused grouped blend-expand (custom_vjp)
# ---------------------------------------------------------------------------
def _grouped_impl(w, B, W, use_kernel: bool):
    if use_kernel:
        LAUNCH_COUNTS.inc("fwd")
        return _blend_expand_grouped(w, B, W, interpret=_interpret())
    return ref.ligo_blend_expand_grouped_ref(w, B, W)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blend_expand_grouped_vjp(use_kernel: bool, w, B, W):
    return _grouped_impl(w, B, W, use_kernel)


def _grouped_fwd(use_kernel, w, B, W):
    return _grouped_impl(w, B, W, use_kernel), (w, B, W)


def _grouped_bwd(use_kernel, res, dP):
    """All three cotangents of P[g,k,e] = B (Σ_l w[g,k,l] W[g,l,e]).

    On TPU: one fused Pallas pass over the dP tiles (dW, dB, dw emitted
    together, small-space scratch accumulation). On CPU: the einsum oracle.
    Either way no widened intermediate stack exists and operands stream at
    param dtype with float32 accumulation.
    """
    w, B, W = res
    if use_kernel:
        LAUNCH_COUNTS.inc("bwd")
        return _bwd_fused(w, B, W, dP, interpret=_interpret())
    return ref.ligo_blend_expand_bwd_ref(w, B, W, dP)


_blend_expand_grouped_vjp.defvjp(_grouped_fwd, _grouped_bwd)


def ligo_blend_expand_grouped_vjp(w, B, W, *, use_kernel=None):
    """Differentiable grouped ``P[g,k,e] = B @ (Σ_l w[g,k,l] W[g,l,e])``.

    w: (G, L2, L1); B: (I, A); W: (G, L1, E, A, Bd) → (G, L2, E, I, Bd).
    ``use_kernel=None`` picks the Pallas kernels on TPU and the einsum
    reference elsewhere; either way gradients flow through the custom VJP
    above (identical contractions, no widened intermediate stack).
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    return _blend_expand_grouped_vjp(bool(use_kernel), w, B, W)


def shard_split(G: int, Bd: int, mesh):
    """How :func:`ligo_blend_expand_grouped_sharded` splits a group's
    ``(G, L1, E, A, Bd)`` stack over ``mesh``: ``(axes_b, axes_g)``, the
    mesh axes that shard the trailing ``Bd`` dim, or else the group dim
    ``G`` (at most one of the two is non-empty; both empty when neither
    divides)."""
    from repro.distributed.sharding import divisible_axes
    axes_b = divisible_axes(Bd, mesh)
    return axes_b, (() if axes_b else divisible_axes(G, mesh))


def ligo_blend_expand_grouped_sharded(w, B, W, mesh, *, use_kernel=None):
    """Grouped blend-expand distributed over ``mesh`` via ``shard_map``.

    Shards the trailing ``Bd`` dim of the leaf stacks — or, when no mesh-axis
    subset divides it, the leaf-group dim ``G`` — so every device runs the
    fused custom_vjp kernel (or the einsum reference) on its local shard with
    zero cross-device traffic: the kernel only contracts ``L1`` (the blend)
    and ``A`` (the expansion), and both stay whole per shard. The expander
    ``B`` always rides replicated (every shard contracts against it whole);
    ``w`` is replicated on the Bd route but shards with the group dim on the
    G fallback (its leading dim is G). Cotangents of replicated operands are
    psum'd by the shard_map transpose, so the route stays differentiable in
    all three operands either way. Falls back to the plain
    (GSPMD-replicated) call when ``mesh`` is None or neither dim is
    divisible.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if mesh is None:
        return ligo_blend_expand_grouped_vjp(w, B, W, use_kernel=use_kernel)
    from jax.sharding import PartitionSpec as P

    axes_b, axes_g = shard_split(W.shape[0], W.shape[-1], mesh)
    if axes_b:
        spec_w = P()
        spec_W = spec_out = P(None, None, None, None, axes_b)
    elif axes_g:
        spec_w = P(axes_g, None, None)
        spec_W = spec_out = P(axes_g, None, None, None, None)
    else:
        return ligo_blend_expand_grouped_vjp(w, B, W, use_kernel=use_kernel)
    fn = jax.shard_map(
        functools.partial(ligo_blend_expand_grouped_vjp,
                          use_kernel=use_kernel),
        mesh=mesh, in_specs=(spec_w, P(), spec_W), out_specs=spec_out,
        check_vma=False)
    return fn(w, B, W)


def ligo_blend_expand_vjp(w, B, W, *, use_kernel=None):
    """Differentiable fused ``P[l2] = B @ (Σ_l w[l2,l] W[l])``.

    Single-leaf convenience wrapper over the grouped custom_vjp (G = E = 1).
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    out = _blend_expand_grouped_vjp(bool(use_kernel), w[None], B,
                                    W[None, :, None])
    return out[0, :, 0]


def paged_kernel_ok(block_size: int, features: int, mesh=None) -> bool:
    """Does a paged decode round read its pools ``(L, n_blocks, block_size,
    features)`` in place with the paged-attention kernel? Only on the TPU
    (elsewhere the gather path, as the LiGO kernels take their reference),
    on one device (no ``pallas_call`` under GSPMD partitioning), and for
    pages of whole tiles (:func:`page_fits`)."""
    return (not _interpret() and (mesh is None or mesh.size == 1)
            and page_fits(block_size, features))


def paged_attention(q, k_new, v_new, k_pool, v_pool, layer, pages, lens):
    """One decode step of attention over stacked paged pools, reading only
    each slot's live pages of ``layer`` (see
    :mod:`repro.kernels.paged_attention`). Counted per trace under
    ``LAUNCH_COUNTS["paged_attn"]``."""
    LAUNCH_COUNTS.inc("paged_attn")
    return _paged_attention(q, k_new, v_new, k_pool, v_pool, layer, pages,
                            lens, interpret=_interpret())


def flash_attention(q, k, v, *, causal=True, window=0, **kw):
    """(B, H, T, dh) × (B, KV, S, dh)² → (B, H, T, dh)."""
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=_interpret(), **kw)


# re-exported oracles (benchmarks compare against these); fused_eligible /
# fused_vmem_bytes re-export directly via the import above
ligo_blend_expand_ref = ref.ligo_blend_expand_ref
ligo_blend_expand_grouped_ref = ref.ligo_blend_expand_grouped_ref
ligo_blend_expand_bwd_ref = ref.ligo_blend_expand_bwd_ref
ligo_grow_ref = ref.ligo_expand_full_ref
flash_attention_ref = ref.flash_attention_ref
