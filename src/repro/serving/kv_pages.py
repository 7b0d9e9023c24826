"""Paged KV-cache allocation: fixed-size blocks + per-slot page tables.

The dense serving layout charges every slot a full ``max_len`` cache row.
Paged allocation replaces the row with fixed-size blocks drawn from a shared
pool: each slot holds a page table (``(max_pages,)`` int32 block ids, ``-1``
= unmapped) and pages are allocated lazily as its sequence grows, so a slot
two tokens into a short prompt pays one block, not ``max_len``.

Split of responsibilities:

- :class:`PageAllocator` is **host-side** bookkeeping (free list, page
  tables, per-slot worst-case reservations). It is pure Python/numpy and is
  never traced — the engine consults it between decode launches.
- The device ops below (:func:`gather_pages`, :func:`write_token_paged`,
  :func:`scatter_row_blocks`) run inside the jitted serving programs against
  pools shaped ``(L, n_blocks, block_size, KV·dh)`` and a traced snapshot
  of the page table. A position's heads lie flat in the last dim, so a
  block is one contiguous, lane-dense ``(block_size, KV·dh)`` slab in HBM:
  the TPU lays a ``(…, KV, dh)`` pool out with the block index minor-most,
  which scatters a block across the whole pool. The one-device decode
  round reads the live blocks in place (``kernels.paged_attention``).

Masking convention (load-bearing): an unmapped page is ``-1`` in the table.
jax gathers treat negative indices numpy-style (they *wrap*), so reads
through an unmapped page return another block's data — which is safe only
because decode attention masks every position ``>= cur_len`` and unmapped
pages can only cover positions beyond the slot's allocated span. Writes
must never land in another slot's block, so write targets are redirected to
``n_blocks`` (one past the pool) — out-of-bounds *scatter* indices are
dropped by XLA, making the write a no-op instead of corruption.

Growth interacts trivially: a hop changes the per-position feature shape
``(KV, dh)`` but never the block geometry, so the allocator and page tables
survive every hop unchanged — migration builds new *pools*, and an aborted
hop discards them (the draft-side pages) without touching the tables.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.models.layers import paged_write_targets


def paged_supported(cfg: ModelConfig) -> bool:
    """Families whose whole decode state is one stacked attention K/V cache
    and whose attention is full-context (a sliding window wants a ring
    buffer, which the dense layout already provides)."""
    return cfg.family in ("dense", "moe", "vlm") and cfg.window == 0


class PageOOM(RuntimeError):
    """The pool cannot back a request's worst-case page demand."""


class PageAllocator:
    """Host-side block allocator: free list + per-slot page tables.

    ``pool_blocks`` defaults to ``slots * max_pages`` (every slot can reach
    ``max_len`` — no admission pressure, memory savings show up as *peak
    allocated* blocks). A smaller pool creates real pressure: admission then
    reserves each request's worst-case page count up front, so an admitted
    request can always finish — backpressure is a deferred admission, never
    a mid-flight OOM (the engine's zero-drop guarantee).
    """

    def __init__(self, slots: int, max_len: int, block_size: int,
                 pool_blocks: Optional[int] = None):
        assert block_size > 0
        self.slots = slots
        self.block_size = block_size
        self.max_pages = -(-max_len // block_size)          # ceil
        self.padded_len = self.max_pages * block_size       # >= max_len
        self.n_blocks = (slots * self.max_pages if pool_blocks is None
                         else int(pool_blocks))
        assert self.n_blocks >= self.max_pages, \
            "pool smaller than one slot's worst case"
        self.table = np.full((slots, self.max_pages), -1, np.int32)
        self.free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self.reserved = np.zeros((slots,), np.int64)   # admission worst case
        self.allocated = np.zeros((slots,), np.int64)
        self.peak_blocks = 0
        self.dirty = True                              # device table stale
        self._device_table = None
        # pool-pressure gauges (host-side bookkeeping → host-side metrics)
        self._g_in_use = obs.gauge("serve.kv.pool_in_use_blocks")
        self._g_peak = obs.gauge("serve.kv.pool_peak_blocks")
        obs.gauge("serve.kv.pool_total_blocks").set(self.n_blocks)
        self._g_in_use.set(0)
        self._g_peak.set(0)

    # -- accounting ---------------------------------------------------------
    def pages_for(self, length: int) -> int:
        return -(-max(0, int(length)) // self.block_size)

    @property
    def in_use(self) -> int:
        return self.n_blocks - len(self.free)

    def _headroom(self) -> int:
        outstanding = int((self.reserved - self.allocated).sum())
        return len(self.free) - outstanding

    # -- lifecycle ----------------------------------------------------------
    def can_admit(self, worst_len: int) -> bool:
        return self._headroom() >= self.pages_for(worst_len)

    def admit(self, slot: int, cur_len: int, worst_len: int) -> None:
        """Reserve ``worst_len`` worth of pages for ``slot`` and back the
        first ``cur_len`` positions now (the prompt insert writes them)."""
        assert self.allocated[slot] == 0, f"slot {slot} not released"
        need = self.pages_for(worst_len)
        if self._headroom() < need:
            raise PageOOM(f"slot {slot}: need {need} pages, "
                          f"headroom {self._headroom()}")
        self.reserved[slot] = need
        self.ensure(slot, cur_len)

    def ensure(self, slot: int, upto: int) -> None:
        """Back positions ``[0, upto)`` of ``slot`` with real blocks."""
        need = min(self.pages_for(upto), self.max_pages)
        while self.allocated[slot] < need:
            if not self.free:
                raise PageOOM(f"slot {slot}: free list empty at "
                              f"{self.allocated[slot]}/{need} pages")
            self.table[slot, self.allocated[slot]] = self.free.pop()
            self.allocated[slot] += 1
            self.dirty = True
        self.peak_blocks = max(self.peak_blocks, self.in_use)
        self._g_in_use.set(self.in_use)
        self._g_peak.set(self.peak_blocks)

    def release(self, slot: int) -> None:
        for j in range(int(self.allocated[slot])):
            self.free.append(int(self.table[slot, j]))
        self.table[slot] = -1
        self.allocated[slot] = 0
        self.reserved[slot] = 0
        self.dirty = True
        self._g_in_use.set(self.in_use)

    # -- device view --------------------------------------------------------
    def device_table(self) -> jax.Array:
        """The page table as a device array, refreshed only when it changed
        (same shape/dtype every time — no retraces)."""
        if self.dirty or self._device_table is None:
            self._device_table = jnp.asarray(self.table)
            self.dirty = False
        return self._device_table

    def bytes_per_slot(self, block_bytes: int) -> float:
        """Peak cache bytes per slot for this run (the BENCH metric)."""
        return self.peak_blocks * block_bytes / max(1, self.slots)


# ---------------------------------------------------------------------------
# Device ops (called inside jitted serving programs)
# ---------------------------------------------------------------------------
def init_paged_caches(cfg: ModelConfig, n_blocks: int,
                      block_size: int) -> Dict[str, jax.Array]:
    """Zeroed K/V pools ``(L, n_blocks, block_size, KV·dh)``."""
    from repro.models.model import DTYPES
    dtype = DTYPES[cfg.dtype]
    shape = (cfg.n_layers, n_blocks, block_size,
             cfg.n_kv_heads * cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gather_pages(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """(n_blocks, bs, *F) gathered through (B, P) → (B, P*bs, *F), F a
    position's features (``KV·dh`` in the engine's pools).

    Unmapped (-1) pages wrap to the pool tail — harmless, those positions
    are ``>= cur_len`` and masked by decode attention (see module doc)."""
    B, P = pages.shape
    bs = pool.shape[1]
    return pool[pages].reshape(B, P * bs, *pool.shape[2:])


def write_token_paged(pool: jax.Array, pages: jax.Array,
                      pos: jax.Array, kv: jax.Array) -> jax.Array:
    """Write one token per slot at its own position through the page table.

    pool: (n_blocks, bs, *F); pages: (B, P); pos: (B,); kv: (B, 1, KV, dh)
    with KV·dh = prod(F). Unmapped targets redirect out of bounds → the
    scatter drops them.
    """
    tgt, off = paged_write_targets(pages, pos, pool.shape[0], pool.shape[1])
    return pool.at[tgt, off].set(kv[:, 0].reshape(kv.shape[:1]
                                                  + pool.shape[2:]))


def scatter_row_blocks(pool: jax.Array, pages_row: jax.Array,
                       row: jax.Array) -> jax.Array:
    """Insert a dense cache row into the pool via one slot's page table.

    pool: (L, n_blocks, bs, *F); pages_row: (P,); row: (L, P*bs, KV, dh)
    — the prefill-produced row padded to the page-aligned length.
    """
    L, n_blocks, bs = pool.shape[:3]
    P = pages_row.shape[0]
    blocks = row.reshape((L, P, bs) + pool.shape[3:])
    tgt = jnp.where(pages_row >= 0, pages_row, n_blocks)
    return pool.at[:, tgt].set(blocks)


def gathered_dense_view(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Materialise the dense ``(L, B, P*bs, *F)`` view of a pool — the
    bridge back to every dense-layout consumer (cache growth oracles,
    parity tests; the engine's pools have F = KV·dh). Unmapped pages come back as whatever block they wrap to;
    callers mask by position exactly like decode attention does."""
    return jax.vmap(lambda pl: gather_pages(pl, table))(pool)
