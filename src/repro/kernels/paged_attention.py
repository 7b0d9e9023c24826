"""Pallas TPU kernel: one decode step of attention over a paged KV pool.

The serving engine keeps every layer's K/V in one stacked block pool per
tensor, ``(L, n_blocks, block_size, KV·dh)``, addressed through per-slot
page tables (``serving.kv_pages``). The plain path gathers each slot's whole
table through the pool, converts it to float32 and runs dense attention —
every round then touches every block of every layer. This kernel reads the
pool where it lies:

- the page table, each slot's pool length and the layer index ride as
  scalar-prefetch operands (SMEM); the pools stay in HBM
  (``memory_space=ANY``) and the kernel DMAs **only the live pages** of the
  slot it works on — pages ``j < ceil(len / block_size)`` of layer
  ``layer`` — so a slot with nothing in the pool reads nothing;
- the grid runs over slots; each slot loops over its live chunks of
  ``ppc`` pages with two VMEM buffers, fetching chunk ``c + 1`` while it
  computes chunk ``c``;
- a page is one contiguous ``(block_size, KV·dh)`` tile row block (the
  heads are flattened into the lane dim), so a DMA moves whole tiles and
  the arithmetic runs on lane-dense operands: the query is laid out as a
  block-diagonal ``(G·KV, KV·dh)`` matrix (row ``g·KV + k`` holds head
  ``(k, g)`` in head ``k``'s lanes), which makes the scores one MXU
  contraction ``(G·KV, KV·dh) × (T, KV·dh)ᵀ`` and ``p·v`` one
  ``(G·KV, T) × (T, KV·dh)``; each row keeps only its own head's lanes;
- online softmax with the running max, sum and accumulator in float32;
  ``q·k`` and ``p·v`` take operands in the pool's dtype and accumulate in
  float32;
- the step's own token is not in the pool yet (the caller writes every
  layer's new K/V into the pool once, after the layer loop): its key and
  value come in as operands and seed the softmax, so the result is
  attention over ``len + 1`` positions.

Validated in interpret mode against ``models.layers.paged_decode_attention``
(tests/test_paged_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# positions per chunk the kernel aims for: one MXU-deep contraction
CHUNK_POSITIONS = 128


def chunk_pages(block_size: int, max_pages: int) -> int:
    """Pages per chunk: ``CHUNK_POSITIONS`` positions, at most a slot's
    whole table, at least one page."""
    return max(1, min(max_pages, CHUNK_POSITIONS // block_size))


def page_fits(block_size: int, features: int) -> bool:
    """Does Mosaic take a page of ``(block_size, features)``? The kernel
    DMAs whole pages into a VMEM buffer at row offsets of ``block_size``
    and slices it by page, so a page must be whole (8, 128) tiles."""
    return block_size % 8 == 0 and features % 128 == 0


def _kernel(layer_ref, lens_ref, pages_ref,            # scalar prefetch
            q_ref, kn_ref, vn_ref, k_hbm, v_hbm,        # inputs
            o_ref,                                      # output
            kbuf, vbuf, sems,                           # scratch
            *, kv_heads: int, d_head: int, ppc: int, max_pages: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    n = lens_ref[b]                                     # positions in pool
    n_blocks, bs = k_hbm.shape[1], k_hbm.shape[2]
    T = ppc * bs
    n_pages = (n + bs - 1) // bs
    n_chunks = (n_pages + ppc - 1) // ppc
    G = q_ref.shape[1]
    F = kv_heads * d_head
    scale = 1.0 / math.sqrt(d_head)

    def dma(c, slot, i):
        page = pages_ref[b * max_pages + c * ppc + i]
        page = jnp.clip(page, 0, n_blocks - 1)          # never leave the pool
        dst = pl.ds(i * bs, bs)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, dst], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, dst], sems.at[1, slot]))

    def fetch(c, slot, wait: bool):
        for i in range(ppc):
            @pl.when(c * ppc + i < n_pages)
            def _():
                for cp in dma(c, slot, i):
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    # block-diagonal query: row g·KV + k carries head (k, g) in k's lanes
    row = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, F), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, F), 1)
    own = (col >= row * d_head) & (col < row * d_head + d_head)
    qf = q_ref[0].astype(jnp.float32)                   # (G, F)
    qt = jnp.concatenate([jnp.where(own, qf[g:g + 1], 0.0)
                          for g in range(G)], axis=0)   # (G·KV, F)
    qk = qt.astype(kbuf.dtype)

    # the step's own token seeds the softmax: m = its score, l = 1, acc = v
    s0 = jnp.sum(qt * kn_ref[0].astype(jnp.float32), axis=1,
                 keepdims=True) * scale                 # (G·KV, 1)
    acc0 = jnp.broadcast_to(vn_ref[0].astype(jnp.float32), qt.shape)

    @pl.when(n_chunks > 0)
    def _():
        fetch(0, 0, wait=False)

    def body(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            fetch(c + 1, 1 - slot, wait=False)

        fetch(c, slot, wait=True)
        k = kbuf[slot]                                  # (T, F)
        v = vbuf[slot]
        s = jax.lax.dot_general(qk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where(pos < n, s, NEG_INF)              # (G·KV, T)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        # rows past the slot's length hold whatever the buffer last held
        rows = c * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        v = jnp.where(rows < n, v.astype(jnp.float32), 0.0).astype(v.dtype)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, body, (s0, jnp.ones_like(s0), acc0))
    out = acc / l                                       # (G·KV, F)
    o = [jnp.sum(jnp.where(own, out[g * kv_heads:(g + 1) * kv_heads], 0.0),
                 axis=0, keepdims=True) for g in range(G)]
    o_ref[0] = jnp.concatenate(o, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
                    pages: jax.Array, lens: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Attention of one new token per slot over its pool history and itself.

    q: (B, H, dh); k_new/v_new: (B, KV, dh) — the step's own K/V;
    k_pool/v_pool: (L, n_blocks, block_size, KV·dh) stacked pools;
    layer: () int32; pages: (B, P) int32 page table (-1 = unmapped);
    lens: (B,) int32 — positions of each slot already in the pool (its
    pages ``< ceil(lens / block_size)`` must be mapped). Returns (B, H, dh)
    in q's dtype. Head ``h`` reads kv head ``h // (H / KV)``.
    """
    B, H, dh = q.shape
    KV = k_new.shape[1]
    G = H // KV
    F = KV * dh
    bs, F2 = k_pool.shape[2:]
    assert F2 == F and v_pool.shape == k_pool.shape, (k_pool.shape, F)
    P = pages.shape[1]
    ppc = chunk_pages(bs, P)
    # (B, H, dh) → (B, G, KV·dh): row g holds heads (·, g) in their lanes
    qg = q.reshape(B, KV, G, dh).transpose(0, 2, 1, 3).reshape(B, G, F)
    kernel = functools.partial(_kernel, kv_heads=KV, d_head=dh, ppc=ppc,
                               max_pages=P)
    row = lambda b, *_: (b, 0, 0)                       # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, G, F), row),
                      pl.BlockSpec((1, 1, F), row),
                      pl.BlockSpec((1, 1, F), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, G, F), row),
            scratch_shapes=[pltpu.VMEM((2, ppc * bs, F), k_pool.dtype),
                            pltpu.VMEM((2, ppc * bs, F), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, G, F), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      pages.reshape(-1).astype(jnp.int32), qg,
      k_new.reshape(B, 1, F), v_new.reshape(B, 1, F), k_pool, v_pool)
    return out.reshape(B, G, KV, dh).transpose(0, 2, 1, 3).reshape(B, H, dh)
