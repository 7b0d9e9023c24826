"""Pallas TPU kernel: fused backward pass of the LiGO blend-expand.

Transpose of ``P[g,k,e] = B (Σ_l w[g,k,l] W[g,l,e])`` — all three cotangents
produced in a **single pass** over the ``dP`` tiles:

    T[g,k,e]   = Bᵀ dP[g,k,e]                      (small-space, VMEM only)
    dW[g,l,e]  = Σ_k w[g,k,l] · T[g,k,e]
    dB         = Σ_{g,k,e} dP[g,k,e] · blendedᵀ,  blended = Σ_l w[g,k,l] W[g,l,e]
    dw[g,k,l]  = Σ_e ⟨T[g,k,e], W[g,l,e]⟩

The LiGO growth phase differentiates through ``apply_ligo`` every SGD step,
so this — not the forward — is the phase's hot loop. The XLA einsum
formulation (kept as the oracle in :func:`repro.kernels.ref.
ligo_blend_expand_bwd_ref`) launches three contractions that re-read ``dP``
from HBM twice and ``W`` twice and round-trips the small-space ``T`` and
``blended`` stacks through HBM; here ``dP``, ``W`` and ``B`` each move
between HBM and VMEM **exactly once per launch** and all cross-tile state
lives in VMEM scratch — no widened ``(L1, D2o, ·)`` or ``(L1, D2o, D1i)``
intermediate ever exists.

Dataflow, grid ``(b, n, k, i)`` with ``n = g·E + e`` and the ``i``
(contraction) dim innermost. The expander ``B`` is resident in VMEM whole
(rows zero-padded to the i-tile outside the kernel) and the small-dim extent
A rides inside every block, so no operand block is ever revisited
non-consecutively — which is what makes the single-streaming true:

- ``T_acc (A, TB)``     rebuilt per (b, n, k): ``+= B[i·TI:,:]ᵀ · dP-tile``
                        over i;
- ``bl (A, TB)``        blended slab for (b, n, k), computed once at i == 0;
- ``dW_acc (L1,A,TB)``  ``+= w-row ⊗ T_acc`` at each k's last i tile, flushed
                        straight to the ``dW`` output block at k == L2-1;
- ``dB_acc (I', A)``    ``+= dP-tile · blᵀ`` rows i·TI.., accumulated across
                        the whole (n, k, i) nest, flushed once per b to a
                        small ``(n_b, I, A)`` partial that one XLA reduction
                        folds to ``dB`` (the only out-of-kernel op);
- ``dw`` partials       ``(n_b, N, L2, L1)``, one tiny row per (b, n, k)
                        column, reduced outside in the small space.

Ragged dims: the only in-kernel masks are the dP tile's ragged i rows /
b cols and the W slab's ragged b cols (block padding is garbage and both
feed contractions); A is always exact in-block and B's padding is real
zeros. Operands stream at param dtype (bf16-safe — no HBM upcast); every
accumulator is float32.

Validated in interpret mode against the einsum oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ligo_expand import (VMEM_LIMIT_BYTES, _pad_rows,
                                       fused_tiles)


def _mask_tail(x, axis: int, valid: int):
    """Zero the (static) ragged tail of ``x`` along ``axis``."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < valid, x, jnp.zeros_like(x))


def _bwd_kernel(w_ref, b_ref, W_ref, dP_ref, dW_ref, dBp_ref, dwp_ref,
                T_acc, bl_ref, dW_acc, dB_acc, *,
                n_n: int, n_k: int, n_i: int, ti: int, tb: int,
                i_dim: int, b_dim: int, L1: int):
    b = pl.program_id(0)
    n = pl.program_id(1)
    k = pl.program_id(2)
    i = pl.program_id(3)
    rag_b = b_dim % tb

    def masked_slab():
        slab = W_ref[0, :, 0].astype(jnp.float32)        # (L1, A, TB)
        if rag_b:
            slab = _mask_tail(slab, 2, b_dim - b * tb)
        return slab

    w_row = w_ref[0, k].astype(jnp.float32)              # (L1,)

    @pl.when((n == 0) & (k == 0) & (i == 0))
    def _zero_db():
        dB_acc[...] = jnp.zeros_like(dB_acc)

    @pl.when((k == 0) & (i == 0))
    def _zero_dw():
        dW_acc[...] = jnp.zeros_like(dW_acc)

    @pl.when(i == 0)
    def _start_k():
        T_acc[...] = jnp.zeros_like(T_acc)
        # blended slab for this (g, k): Σ_l w[g,k,l] W[g,l,e] — (A, TB)
        bl_ref[...] = jax.lax.dot_general(
            w_row[None, :], masked_slab().reshape(L1, -1),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bl_ref.shape)

    dp = dP_ref[0, 0, 0].astype(jnp.float32)             # (TI, TB)
    if i_dim % ti:
        dp = _mask_tail(dp, 0, i_dim - i * ti)
    if rag_b:
        dp = _mask_tail(dp, 1, b_dim - b * tb)
    Bsl = b_ref[pl.ds(i * ti, ti), :]                    # (TI, A), zero-pad

    # T[g,k,e] rows: (A, TI) x (TI, TB) -> (A, TB)
    T_acc[...] += jax.lax.dot_general(
        Bsl.astype(jnp.float32), dp, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dB rows for this i tile: (TI, TB) x (TB, A)ᵀ -> (TI, A)
    dB_acc[pl.ds(i * ti, ti), :] += jax.lax.dot_general(
        dp, bl_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _end_k():
        T = T_acc[...]
        # dW[l] += w[k,l] · T — (L1, 1) x (1, A·TB), an MXU outer product
        dW_acc[...] += jax.lax.dot(
            w_row[:, None], T.reshape(1, -1),
            preferred_element_type=jnp.float32).reshape(dW_acc.shape)
        # dw[g, k, :] partial for this b tile: ⟨T, W[l]⟩ — (L1,)
        dwp_ref[0, 0, k] = jax.lax.dot_general(
            masked_slab().reshape(L1, -1), T.reshape(-1),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == n_k - 1)
        def _flush_dw():
            dW_ref[0, :, 0] = dW_acc[...].astype(dW_ref.dtype)

        @pl.when((n == n_n - 1) & (k == n_k - 1))
        def _flush_db():
            dBp_ref[0] = dB_acc[:i_dim, :]


@functools.partial(jax.jit, static_argnames=("ti", "tb", "interpret"))
def ligo_blend_expand_bwd_fused(w: jax.Array, B: jax.Array, W: jax.Array,
                                dP: jax.Array, *, ti: int = 128,
                                tb: int = 128, interpret: bool = False):
    """Fused cotangents of ``ligo_blend_expand_grouped``.

    w: (G, L2, L1); B: (I, A); W: (G, L1, E, A, Bd); dP: (G, L2, E, I, Bd)
    → (dw (G, L2, L1), dB (I, A), dW (G, L1, E, A, Bd)).
    """
    G, L2, L1 = w.shape
    I, A = B.shape
    G2, L1b, E, A2, Bd = W.shape
    assert G2 == G and L1b == L1 and A2 == A, (w.shape, B.shape, W.shape)
    assert dP.shape == (G, L2, E, I, Bd), (dP.shape, (G, L2, E, I, Bd))
    ti, tb = fused_tiles(I, Bd, ti=ti, tb=tb)
    n_i, n_b = pl.cdiv(I, ti), pl.cdiv(Bd, tb)
    i_pad = n_i * ti
    N = G * E
    B_pad = _pad_rows(B, i_pad)

    grid = (n_b, N, L2, n_i)
    kernel = functools.partial(
        _bwd_kernel, n_n=N, n_k=L2, n_i=n_i, ti=ti, tb=tb,
        i_dim=I, b_dim=Bd, L1=L1)
    dW, dBp, dwp = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L2, L1), lambda b, n, k, i: (n // E, 0, 0)),
            pl.BlockSpec((i_pad, A), lambda b, n, k, i: (0, 0)),
            pl.BlockSpec((1, L1, 1, A, tb),
                         lambda b, n, k, i: (n // E, 0, n % E, 0, b)),
            pl.BlockSpec((1, 1, 1, ti, tb),
                         lambda b, n, k, i: (n // E, k, n % E, i, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, L1, 1, A, tb),
                         lambda b, n, k, i: (n // E, 0, n % E, 0, b)),
            pl.BlockSpec((1, I, A), lambda b, n, k, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, L2, L1), lambda b, n, k, i: (b, n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, L1, E, A, Bd), W.dtype),
            jax.ShapeDtypeStruct((n_b, I, A), jnp.float32),
            jax.ShapeDtypeStruct((n_b, N, L2, L1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((A, tb), jnp.float32),        # T_acc
            pltpu.VMEM((A, tb), jnp.float32),        # blended
            pltpu.VMEM((L1, A, tb), jnp.float32),    # dW accumulator
            pltpu.VMEM((i_pad, A), jnp.float32),     # dB accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(w.astype(jnp.float32), B_pad, W, dP)

    # small-space partial reductions (the only out-of-kernel work)
    dB = dBp.sum(0).astype(B.dtype)
    dw = dwp.sum(0).reshape(G, E, L2, L1).sum(1).astype(w.dtype)
    return dw, dB, dW
