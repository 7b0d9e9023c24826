"""Pallas TPU kernel: fused LiGO depth-blend + width-expansion (forward).

Computes ``P[g, l2, e] = B @ (Σ_l w[g, l2, l] · W[g, l, e])`` — the growth
hot-spot. The torch reference implementation materialises the widened stack
(L1, D2, D2) in HBM and then blends along depth; on TPU we exploit that the
blend commutes with the (layer-independent) width expansion and fuse the
blend into the matmul's rhs operand:

- grid ``(b, n, l2, i)`` with ``n = g·E + e`` — the *leaf-group* dim G (same
  shape + expander pair leaves batched by the GrowthPlan) and the MoE expert
  dim E are folded into the grid, so a whole group of 4-D ``(L1, E, a, b)``
  expert stacks executes as **one** kernel launch;
- the expander ``B`` is held in VMEM whole (rows zero-padded to the i-tile
  outside the kernel — real zeros, so no masking is ever needed) and the
  small-dim extent A rides inside each block, which removes the ``a`` grid
  dim: every operand's block index changes on every revisit-run boundary, so
  **W, B and the output each move between HBM and VMEM exactly once per
  launch** — the blended stack never exists in HBM and nothing is re-fetched;
- per grid step the kernel blends the (L1, A, TB) slab of the *small* weight
  stack with the ``w[g, l2]`` row once per (b, n, l2) (a vector FMA, VPU work
  overlapped with the MXU matmul) and contracts the full-A tile
  ``B[i·TI:, :] @ blended`` straight on the MXU;
- non-128-aligned dims need no special casing: dims ≤ 128 are a single
  block, the ragged last i/b tiles are handled by Pallas' block padding
  (garbage only ever lands in out-of-range output rows/cols, which the store
  masks), and A is always exact in-block.

Eligibility is therefore not an alignment question: any ``(L1[, E], a, b)``
stacked leaf with an in-expander qualifies, bounded only by the VMEM budget
(:func:`fused_vmem_bytes` — the backward kernel's resident ``B``/``dB``
accumulators are the binding constraint, see
:mod:`repro.kernels.ligo_expand_bwd`).

Validated in interpret mode against ref.ligo_blend_expand_grouped_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_tile(d: int, cap: int) -> int:
    """One full block for small dims (no padding), cap-tiles above."""
    return d if d <= cap else cap


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """Zero-pad dim 0 of ``x`` up to ``rows`` (real zeros — contraction-safe)."""
    if x.shape[0] == rows:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def fused_tiles(i: int, b: int, *, ti: int = 128, tb: int = 128):
    """Effective (TI, TB) tile sizes for the fused fwd/bwd kernels (the A
    extent always rides whole inside each block)."""
    return _pick_tile(i, ti), _pick_tile(b, tb)


# Scoped-VMEM limit both fused kernels ask Mosaic for (v5e's default scoped
# limit is 16 MiB of its 128 MiB). ``fused_eligible`` admits exactly the
# shapes whose modelled residency fits under it.
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _vmem_tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one buffer: Mosaic pads the last two dims to whole
    (sublane, 128-lane) tiles — 8 rows of 32-bit, 16 of 16-bit values."""
    *lead, rows, cols = shape
    sub = 8 * (4 // itemsize)
    n = 1
    for d in lead:
        n *= d
    return n * (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def fused_vmem_bytes(L1: int, L2: int, i: int, a: int, b: int, *,
                     G: int = 2, itemsize: int = 4) -> int:
    """VMEM (bytes) the fwd and bwd kernels ask Mosaic for, the larger of
    the two, with every buffer padded to whole tiles: the operand and output
    blocks (two buffers each, one where the block index is grid-invariant),
    the f32 scratch accumulators and the large f32 values of the kernel
    body. ``G`` is the grid's folded leaf × expert count (``G == 1`` makes
    the ``w`` block, and with one b tile the ``W`` block, grid-invariant)
    and ``itemsize`` the parameter dtype's; ``w`` and every accumulator are
    float32.

    On v5e the forward count matches the compiler's scoped allocation for
    plain (E = 1) leaves. The backward count is an upper bound: XLA may
    place the small ``dB`` partial output in VMEM itself, and then the
    kernel needs no buffers for it. The bwd kernel dominates — it holds the
    padded expander ``B``, the (I, A) ``dB`` accumulator and its partial
    output, and the (L1, A, TB) ``dW`` accumulator."""
    ti, tb = fused_tiles(i, b)
    i_pad = -(-i // ti) * ti
    n_b = -(-b // tb)
    t = _vmem_tile_bytes
    # a block whose index never changes over the grid gets one buffer,
    # every other block two (the pipeline prefetches the next one)
    w_bufs = 1 if G == 1 else 2
    W_bufs = 1 if G == 1 and n_b == 1 else 2
    w_blk, B_blk = t((L2, L1), 4), t((i_pad, a), itemsize)
    W_blk, tile = L1 * t((a, tb), itemsize), t((ti, tb), itemsize)
    acc = t((a, tb), 4)
    fwd = w_bufs * w_blk + B_blk + W_bufs * W_blk + 2 * tile + acc
    bwd = (w_bufs * w_blk + B_blk + W_bufs * W_blk + 2 * tile        # ins
           + 2 * (W_blk + t((i, a), 4) + t((L2, L1), 4))            # outs
           + 2 * acc + L1 * acc + t((i_pad, a), 4)                  # scratch
           # f32 values of the body: the (L1, A·TB) dW update, a copy of
           # T, the upcast dP tile
           + L1 * acc + acc + t((ti, tb), 4))
    return max(fwd, bwd)


def fused_eligible(L1: int, L2: int, E: int, i: int, a: int, b: int, *,
                   G: int = 2, itemsize: int = 4) -> bool:
    """Can (L1[, E], a, b) stacked leaves run on the fused fwd+bwd kernels?

    Universal in shape — G and E fold into the grid, ragged dims are handled
    by block padding / pre-padded zeros — so the only rejections are
    degenerate dims and shapes whose VMEM residency exceeds the limit the
    kernels compile under. ``G`` (leaves in the group) and ``itemsize``
    (parameter dtype) default to the conservative side.
    """
    if min(L1, L2, E, i, a, b) < 1:
        return False
    return fused_vmem_bytes(L1, L2, i, a, b, G=G * E,
                            itemsize=itemsize) <= VMEM_LIMIT_BYTES


def _kernel(w_ref, b_ref, W_ref, out_ref, bl_ref, *, L1: int, ti: int):
    k = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _blend():
        # blend the small stack slab for this (g, l2): (A, TB) — once per
        # (b, n, l2), VPU work overlapped with the MXU contraction below
        w_row = w_ref[0, k]                              # (L1,)
        slab = W_ref[0, :, 0]                            # (L1, A, TB)
        bl_ref[...] = jax.lax.dot_general(
            w_row[None, :], slab.reshape(L1, -1),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(bl_ref.shape)

    # expand: (TI, A) @ (A, TB) -> (TI, TB); B rows are pre-padded zeros, so
    # the slice is always in-bounds and ragged-i rows contract to zero
    Bsl = b_ref[pl.ds(i * ti, ti), :]
    out_ref[0, 0, 0] = jax.lax.dot(
        Bsl.astype(jnp.float32), bl_ref[...],
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ti", "ta", "tb", "interpret"))
def ligo_blend_expand_grouped(w: jax.Array, B: jax.Array, W: jax.Array, *,
                              ti: int = 128, ta: int = 128, tb: int = 128,
                              interpret: bool = False) -> jax.Array:
    """w: (G, L2, L1); B: (I, A); W: (G, L1, E, A, Bd) → (G, L2, E, I, Bd).

    One launch for a whole leaf group: G same-shape leaves sharing one
    in-expander, each leaf an (L1, E, A, Bd) expert stack (E = 1 for plain
    2-D-per-layer leaves). The MoE expert dim never broadcasts the blend —
    ``w`` is per-leaf, shared across experts via the grid index map.
    (``ta`` is accepted for API stability; the A extent is never tiled.)
    """
    del ta                                 # A always rides whole in-block
    G, L2, L1 = w.shape
    I, A = B.shape
    G2, L1b, E, A2, Bd = W.shape
    assert G2 == G and L1b == L1 and A2 == A, (w.shape, B.shape, W.shape)
    ti, tb = fused_tiles(I, Bd, ti=ti, tb=tb)
    n_i, n_b = pl.cdiv(I, ti), pl.cdiv(Bd, tb)
    B_pad = _pad_rows(B, n_i * ti)

    grid = (n_b, G * E, L2, n_i)
    kernel = functools.partial(_kernel, L1=L1, ti=ti)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L2, L1), lambda b, n, k, i: (n // E, 0, 0)),
            pl.BlockSpec((n_i * ti, A), lambda b, n, k, i: (0, 0)),
            pl.BlockSpec((1, L1, 1, A, tb),
                         lambda b, n, k, i: (n // E, 0, n % E, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, ti, tb),
                               lambda b, n, k, i: (n // E, k, n % E, i, b)),
        out_shape=jax.ShapeDtypeStruct((G, L2, E, I, Bd), B.dtype),
        scratch_shapes=[pltpu.VMEM((A, tb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(w.astype(jnp.float32), B_pad, W)


def ligo_blend_expand(w: jax.Array, B: jax.Array, W: jax.Array, *,
                      ti: int = 128, ta: int = 128, tb: int = 128,
                      interpret: bool = False) -> jax.Array:
    """w: (L2, L1); B: (D2o, D1o); W: (L1, D1o, D1i) → (L2, D2o, D1i).

    Single-leaf convenience wrapper over the grouped kernel (G = E = 1).
    """
    out = ligo_blend_expand_grouped(w[None], B, W[None, :, None],
                                    ti=ti, ta=ta, tb=tb, interpret=interpret)
    return out[0, :, 0]
