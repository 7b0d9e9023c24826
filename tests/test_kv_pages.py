"""Paged KV-cache allocation: allocator invariants + device-lane parity.

The allocator property (never alias a block across slots, always recycle
freed blocks, honor admission reservations) is driven two ways: a
hypothesis strategy over random admit/ensure/release programs when
hypothesis is installed (CI), and an always-on seeded-random sweep with the
same checker otherwise. Decode parity (paged gather/scatter vs the dense
oracle row cache) runs on both tier-1 device lanes via the mesh fixture.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import BERT_SMALL
from repro.models import init_params
from repro.serving import PageAllocator, PageOOM, ServingEngine
from repro.serving.kv_pages import (gather_pages, gathered_dense_view,
                                    init_paged_caches, scatter_row_blocks,
                                    write_token_paged)

TINY = BERT_SMALL.scaled(
    name="kvp-tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="float32",
    objective="clm", encoder_only=False, causal=True)

MESHES = [((1,), ("data",)), ((2, 4), ("data", "model"))]
MESH_IDS = ["1dev", "2x4"]


# ---------------------------------------------------------------------------
# Allocator invariants (host-side property)
# ---------------------------------------------------------------------------
def _check_invariants(a: PageAllocator):
    mapped = a.table[a.table >= 0]
    # no aliasing: every mapped block id appears exactly once
    assert len(mapped) == len(set(mapped.tolist()))
    # conservation: free + mapped == pool
    assert len(a.free) + len(mapped) == a.n_blocks
    assert set(a.free).isdisjoint(set(mapped.tolist()))
    # per-slot prefix structure: allocated pages are a dense prefix
    for s in range(a.slots):
        n = int(a.allocated[s])
        assert (a.table[s, :n] >= 0).all()
        assert (a.table[s, n:] == -1).all()
        assert a.reserved[s] <= a.max_pages
    # headroom never negative (reservations are backed)
    assert a._headroom() >= 0


def _run_program(a: PageAllocator, ops):
    """Drive (op, slot, length) tuples through the allocator, checking the
    invariants after every step; returns ids of blocks seen freed at least
    once that later got remapped (recycling evidence)."""
    live = set()
    freed_ever, recycled = set(), set()
    for op, slot, length in ops:
        if op == "admit" and slot not in live:
            if a.can_admit(length):
                before = set(a.free)
                a.admit(slot, min(length, a.block_size), length)
                live.add(slot)
                recycled |= (before - set(a.free)) & freed_ever
            else:
                with pytest.raises(PageOOM):
                    a.admit(slot, min(length, a.block_size), length)
        elif op == "ensure" and slot in live:
            upto = min(length, int(a.reserved[slot]) * a.block_size)
            try:
                a.ensure(slot, upto)
            except PageOOM:
                # only possible when over-reserved slots hold the free list
                assert not a.free
        elif op == "release" and slot in live:
            freed_ever |= {int(b) for b in a.table[slot] if b >= 0}
            a.release(slot)
            live.discard(slot)
        _check_invariants(a)
    return recycled


def _random_ops(rng, n, slots, max_len):
    return [(rng.choice(["admit", "ensure", "release"]),
             int(rng.randint(0, slots)), int(rng.randint(1, max_len + 1)))
            for _ in range(n)]


def test_allocator_random_programs_never_alias_and_recycle():
    rng = np.random.RandomState(0)
    recycled_any = False
    for trial in range(30):
        slots = int(rng.randint(1, 5))
        max_len = int(rng.randint(4, 64))
        bs = int(rng.choice([1, 4, 16]))
        pool = int(rng.randint(-(-max_len // bs),
                               slots * -(-max_len // bs) + 1))
        a = PageAllocator(slots, max_len, bs, pool_blocks=pool)
        recycled_any |= bool(_run_program(a, _random_ops(rng, 40, slots,
                                                         max_len)))
    assert recycled_any  # freed blocks really do come back into service


def test_allocator_hypothesis_property():
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (optional dev dep)")
    from hypothesis import given, settings, strategies as st

    op = st.tuples(st.sampled_from(["admit", "ensure", "release"]),
                   st.integers(0, 3), st.integers(1, 48))

    @given(ops=st.lists(op, min_size=1, max_size=60),
           bs=st.sampled_from([1, 3, 8, 16]),
           pool_frac=st.floats(0.34, 1.0))
    @settings(max_examples=50, deadline=None)
    def prop(ops, bs, pool_frac):
        max_pages = -(-48 // bs)
        pool = max(max_pages, int(4 * max_pages * pool_frac))
        a = PageAllocator(4, 48, bs, pool_blocks=pool)
        _run_program(a, ops)

    prop()


def test_admission_reservation_guarantees_completion():
    """A pool big enough for one slot's worst case admits exactly one
    request at a time; the admitted one can always reach its reservation."""
    a = PageAllocator(slots=2, max_len=32, block_size=8, pool_blocks=5)
    assert a.can_admit(32)
    a.admit(0, 8, 32)
    assert not a.can_admit(32)            # headroom spoken for
    assert a.can_admit(8)                 # a small request still fits
    a.ensure(0, 32)                       # the reservation is real
    a.release(0)
    assert a.can_admit(32)                # blocks recycled


# ---------------------------------------------------------------------------
# Device ops: paged read/write vs the dense oracle
# ---------------------------------------------------------------------------
def test_paged_write_gather_roundtrip():
    bs, n_blocks, KV, dh, B, P = 4, 8, 2, 3, 2, 3
    rng = np.random.RandomState(1)
    pool = jnp.zeros((n_blocks, bs, KV, dh), jnp.float32)
    pages = jnp.asarray([[0, 1, -1], [2, 3, 4]], jnp.int32)
    dense = np.zeros((B, P * bs, KV, dh), np.float32)
    for pos in range(2 * bs):             # only mapped positions
        kv = rng.randn(B, 1, KV, dh).astype(np.float32)
        pool = write_token_paged(pool, pages, jnp.full((B,), pos,
                                                       jnp.int32),
                                 jnp.asarray(kv))
        dense[:, pos] = kv[:, 0]
    got = np.asarray(gather_pages(pool, pages))
    np.testing.assert_array_equal(got[:, :2 * bs], dense[:, :2 * bs])
    # a write through slot 0's unmapped third page (positions 2*bs..) must
    # drop for that slot — the OOB redirect — while slot 1's mapped write
    # lands; no other block may change
    before = np.asarray(pool).copy()
    kv = rng.randn(B, 1, KV, dh).astype(np.float32)
    pool = write_token_paged(pool, pages, jnp.full((B,), 2 * bs, jnp.int32),
                             jnp.asarray(kv))
    after = np.asarray(pool)
    np.testing.assert_array_equal(after[4, 0], kv[1, 0])   # slot 1, page 4
    mask = np.ones(n_blocks, bool)
    mask[4] = False
    np.testing.assert_array_equal(after[mask], before[mask])


def test_scatter_row_blocks_lands_only_in_mapped_pages():
    L, n_blocks, bs, KV, dh, P = 2, 6, 4, 2, 3, 2
    rng = np.random.RandomState(2)
    pool = jnp.asarray(rng.randn(L, n_blocks, bs, KV, dh), jnp.float32)
    before = np.asarray(pool).copy()
    row = jnp.asarray(rng.randn(L, P * bs, KV, dh), jnp.float32)
    pages = jnp.asarray([3, -1], jnp.int32)
    out = np.asarray(scatter_row_blocks(pool, pages, row))
    np.testing.assert_array_equal(out[:, 3], np.asarray(row).reshape(
        L, P, bs, KV, dh)[:, 0])
    mask = np.ones(n_blocks, bool)
    mask[3] = False
    np.testing.assert_array_equal(out[:, mask], before[:, mask])


@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
def test_paged_vs_dense_decode_logits(mesh_factory, mesh_def):
    """The acceptance criterion: identical workloads through a paged and a
    dense engine produce the same decode logits to 1e-6 on both lanes (on
    one device they are bit-equal in practice; the bound covers multi-device
    reassociation)."""
    mesh = mesh_factory(*mesh_def)
    params = init_params(TINY, jax.random.PRNGKey(0))

    def run(layout):
        eng = ServingEngine(params, TINY, slots=2, prompt_budget=8,
                            gen_budget=12, kv_layout=layout, mesh=mesh)
        rng = np.random.RandomState(0)
        reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 4 + i % 4)),
                           max_new=12) for i in range(4)]
        while eng.has_work():
            eng.step()
        assert all(r.status == "done" for r in reqs)
        return [r.tokens for r in reqs]

    assert run("paged") == run("dense")


def test_gathered_dense_view_matches_engine_history():
    """The dense view of a live paged engine's pools equals the dense
    engine's cache over every valid position."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    pe = ServingEngine(params, TINY, slots=2, prompt_budget=8, gen_budget=8,
                       kv_layout="paged")
    de = ServingEngine(params, TINY, slots=2, prompt_budget=8, gen_budget=8,
                       kv_layout="dense")
    for eng in (pe, de):
        rng = np.random.RandomState(0)
        for i in range(2):
            eng.submit(list(rng.randint(0, TINY.vocab_size, 5 + i)),
                       max_new=8)
        for _ in range(3):
            eng.step()
    view = np.asarray(gathered_dense_view(pe.state["caches"]["k"],
                                          pe.alloc.device_table()))
    dense = np.asarray(de.state["caches"]["k"])
    # the pools keep a position's heads flat: (L, B, S, KV·dh)
    dense = dense.reshape(dense.shape[:3] + (-1,))
    for s in range(2):
        n = int(pe.pos_host[s])
        assert n == int(de.pos_host[s]) and n > 0
        np.testing.assert_array_equal(view[:, s, :n], dense[:, s, :n])


def test_pool_pressure_defers_but_never_drops():
    """A pool that fits one worst-case request at a time serves all
    submitted requests to completion — admission defers, nothing drops."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(params, TINY, slots=2, prompt_budget=8, gen_budget=8,
                        kv_layout="paged", block_size=4,
                        pool_blocks=4)       # one slot's worst case
    rng = np.random.RandomState(0)
    reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 6)), max_new=8)
            for _ in range(4)]
    deferred = False
    for _ in range(400):
        if not eng.has_work():
            break
        eng.step()
        deferred |= (len(eng.queue) > 0
                     and any(r is None for r in eng.slot_req))
    assert all(r.status == "done" for r in reqs)
    assert eng.counts()["dropped"] == 0 and eng.queue.rejected == 0
    assert deferred                       # the pool really was the bottleneck
    assert eng.alloc.peak_blocks <= 4


def test_paged_bytes_per_slot_below_dense_for_mixed_lengths():
    """Mixed-length workload: peak paged bytes/slot strictly under the dense
    layout's constant max_len row (the BENCH criterion, at test scale)."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(params, TINY, slots=4, prompt_budget=16,
                        gen_budget=16, kv_layout="paged", block_size=4)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size,
                                        int(rng.randint(2, 17)))),
                       max_new=int(rng.randint(1, 6))) for _ in range(8)]
    while eng.has_work():
        eng.step()
    assert all(r.status == "done" for r in reqs)
    pool = eng.state["caches"]["k"]
    elt = jnp.dtype(pool.dtype).itemsize
    block_bytes = 2 * pool.shape[0] * int(np.prod(pool.shape[2:])) * elt
    dense_bytes = block_bytes // eng.alloc.block_size * eng.cap
    assert eng.alloc.bytes_per_slot(block_bytes) < dense_bytes


def test_unsupported_family_falls_back_to_dense():
    win = TINY.scaled(name="kvp-win", window=8)
    params = init_params(win, jax.random.PRNGKey(0))
    eng = ServingEngine(params, win, slots=2, prompt_budget=8, gen_budget=4,
                        kv_layout="paged")
    assert eng.kv_layout == "dense" and eng.alloc is None
    eng.submit([1, 2, 3], max_new=4)
    while eng.has_work():
        eng.step()
    assert eng.counts()["done"] == 1
