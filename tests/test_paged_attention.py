"""The paged-attention decode kernel and the in-place decode round.

The kernel (interpret mode here) against ``paged_decode_attention``, the
gather-and-mask oracle, over random page tables; then the structure of the
one-device decode program: the layers read the stacked pools in place (no
per-layer pool slice, no float32 pool), the kernel is traced, and the
engine's pools are donated across a round. The compile for a described TPU
lives in ``test_tpu_compile.py`` with the other TPU compiles.
"""
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import BERT_SMALL
from repro.kernels import LAUNCH_COUNTS
from repro.kernels.paged_attention import paged_attention
from repro.models import init_params
from repro.models.layers import paged_decode_attention
from repro.serving import ServingEngine
from repro.serving.engine import make_serving_fns
from repro.serving.kv_pages import (gathered_dense_view, init_paged_caches,
                                    write_token_paged)

# f32 pools: the kernel sums in another order than the oracle, nothing else.
# bf16 pools: p·v takes p in bf16 (as the MXU does), relative error 2^-8.
TOL = {"f32": dict(rtol=1e-5, atol=1e-5), "bf16": dict(rtol=2e-2, atol=2e-2)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}

BS, P, L, KV, DH = 4, 5, 2, 2, 8
CAP = P * BS


def _case(G, length, dtype, seed):
    """Three slots: slot 0 at ``cur_len = length``, slot 1 at another
    length, slot 2 inactive (cur_len 1, every page unmapped). Mapped pages
    back positions ``[0, cur_len)``; the rest of each row is -1."""
    rng = np.random.RandomState(seed)
    H, F = KV * G, KV * DH
    n_blocks = 3 * P + 2
    cur = np.array([length, int(rng.randint(1, CAP + 1)), 1], np.int32)
    pages = np.full((3, P), -1, np.int32)
    perm, k = rng.permutation(n_blocks), 0
    for b in range(2):
        need = -(-cur[b] // BS)
        pages[b, :need] = perm[k:k + need]
        k += need
    dt = DTYPES[dtype]
    arr = lambda *s: jnp.asarray(rng.randn(*s), dt)  # noqa: E731
    return (arr(3, H, DH), arr(3, KV, DH), arr(3, KV, DH),
            arr(L, n_blocks, BS, F), arr(L, n_blocks, BS, F),
            jnp.asarray(pages), jnp.asarray(cur))


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("length", ["1", "bs", "bs+1", "cap"])
@pytest.mark.parametrize("G", [1, 4])
def test_kernel_matches_paged_decode_attention(G, length, dtype):
    n = {"1": 1, "bs": BS, "bs+1": BS + 1, "cap": CAP}[length]
    q, kn, vn, kp, vp, pages, cur = _case(G, n, dtype,
                                          seed=G * 100 + n)
    layer = 1
    got = np.asarray(paged_attention(q, kn, vn, kp, vp, jnp.asarray(layer),
                                     pages, cur - 1, interpret=True),
                     np.float32)
    # the oracle: write the step's token into the layer's pool, then gather
    # and mask over cur_len positions
    kl = write_token_paged(kp[layer], pages, cur - 1, kn[:, None])
    vl = write_token_paged(vp[layer], pages, cur - 1, vn[:, None])
    want = np.asarray(paged_decode_attention(q[:, None], kl, vl, pages, cur),
                      np.float32)[:, 0]
    np.testing.assert_allclose(got[:2], want[:2], **TOL[dtype])
    # the inactive slot reads no page: it attends to its own token alone
    own = np.repeat(np.asarray(vn[2], np.float32), G, axis=0)
    np.testing.assert_allclose(got[2], own, **TOL[dtype])
    assert np.isfinite(got).all()


TINY = BERT_SMALL.scaled(
    name="pa-tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="bfloat16",
    objective="clm", encoder_only=False, causal=True)
N_BLOCKS = 13          # appears in no other dim of the program


def _lowered(paged_kernel: bool, cfg=TINY) -> str:
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = {"caches": init_paged_caches(cfg, N_BLOCKS, BS),
             "pos": jnp.zeros((3,), jnp.int32),
             "pages": jnp.full((3, P), -1, jnp.int32)}
    _, decode, _ = make_serving_fns(cfg, CAP, "paged", False, paged_kernel)
    return decode.lower(params, state, jnp.zeros((3, 1), jnp.int32)).as_text()


def _pool_shaped(text: str):
    """Arrays of the program with the pools' block count among their dims:
    (dims, dtype)."""
    return {(dims, dt) for dims, dt in
            re.findall(r"tensor<([0-9x]+)x(f32|bf16)>", text)
            if str(N_BLOCKS) in dims.split("x")}


def test_decode_round_reads_pools_in_place():
    """The kernel path's decode program holds the stacked pools and
    nothing else of their size: no per-layer slice, no float32 copy. The
    gather path, lowered the same way, shows the per-layer slices the
    check looks for."""
    before = LAUNCH_COUNTS.get("paged_attn")
    shaped = _pool_shaped(_lowered(True))
    assert LAUNCH_COUNTS.get("paged_attn") > before
    stacked = f"{TINY.n_layers}x{N_BLOCKS}x{BS}x{KV * DH}"
    assert shaped == {(stacked, "bf16")}, shaped
    gathered = _pool_shaped(_lowered(False))
    assert (f"{N_BLOCKS}x{BS}x{KV * DH}", "bf16") in gathered


@pytest.fixture
def kernel_on(monkeypatch):
    """Have engines take the kernel path as on one TPU (the kernel itself
    runs in interpret mode here)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "paged_kernel_ok", lambda *a, **k: True)


def test_untiled_pages_take_the_gather_path(monkeypatch):
    """The engine reads the pools in place only on the TPU, on one device,
    and for pages of whole (8, 128) tiles; otherwise it gathers through the
    table. Compiled for the TPU (not interpreted), TINY's pages (4
    positions of 16 features) are not whole tiles: the engine's round holds
    per-layer pool slices and traces no kernel."""
    from repro.kernels import ops
    one, two = SimpleNamespace(size=1), SimpleNamespace(size=2)   # meshes
    assert not ops.paged_kernel_ok(16, 128)          # off the TPU
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert ops.paged_kernel_ok(16, 128) and ops.paged_kernel_ok(16, 128, one)
    assert not ops.paged_kernel_ok(16, 128, two)
    assert not ops.paged_kernel_ok(4, 128)
    assert not ops.paged_kernel_ok(16, 64)
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(params, TINY, slots=3, prompt_budget=8, gen_budget=8,
                        block_size=BS)
    assert not eng.paged_kernel(TINY)
    before = LAUNCH_COUNTS.get("paged_attn")
    text = eng._decode.lower(params, eng.state,
                             jnp.zeros((3, 1), jnp.int32)).as_text()
    assert LAUNCH_COUNTS.get("paged_attn") == before
    assert (f"{eng.alloc.n_blocks}x{BS}x{KV * DH}", "bf16") in {
        (dims, dt) for dims, dt in
        re.findall(r"tensor<([0-9x]+)x(f32|bf16)>", text)}


def test_engine_donates_pools_across_a_round(kernel_on):
    """A decode round and an admission each spend the engine's pools and
    leave new ones; the page table and positions survive."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(params, TINY, slots=3, prompt_budget=8, gen_budget=8,
                        block_size=BS)
    assert eng.paged_kernel(TINY)
    eng.submit([1, 2, 3], max_new=6)
    old = eng.state["caches"]
    eng.step()                                   # admission + one round
    assert all(a.is_deleted() for a in jax.tree.leaves(old))
    table = eng.alloc.device_table()
    old = eng.state["caches"]
    eng.step()
    assert all(a.is_deleted() for a in jax.tree.leaves(old))
    assert not table.is_deleted()
    eng.run()
    assert eng.counts()["done"] == 1


F32_TINY = TINY.scaled(name="pa-tiny-f32", n_kv_heads=4, dtype="float32")


def test_kernel_engine_matches_dense_engine_history(kernel_on):
    """An engine whose rounds run the kernel holds the dense engine's cache:
    bit for bit where no attention output went in (every layer's prompt
    positions, and layer 0 throughout), and to float32 rounding at the
    later layers' decoded positions, which the kernel sums in another order
    than the dense path (rtol 1e-5, atol 1e-6)."""
    params = init_params(F32_TINY, jax.random.PRNGKey(0))
    pe = ServingEngine(params, F32_TINY, slots=2, prompt_budget=8,
                       gen_budget=8, kv_layout="paged")
    assert pe.paged_kernel(F32_TINY)
    de = ServingEngine(params, F32_TINY, slots=2, prompt_budget=8,
                       gen_budget=8, kv_layout="dense")
    for eng in (pe, de):
        rng = np.random.RandomState(0)
        for i in range(2):
            eng.submit(list(rng.randint(0, F32_TINY.vocab_size, 5 + i)),
                       max_new=8)
        for _ in range(3):
            eng.step()
    view = np.asarray(gathered_dense_view(pe.state["caches"]["k"],
                                          pe.alloc.device_table()))
    dense = np.asarray(de.state["caches"]["k"])
    dense = dense.reshape(dense.shape[:3] + (-1,))
    for s in range(2):
        n = int(pe.pos_host[s])
        assert n == int(de.pos_host[s]) and n > 5 + s
        prompt = 5 + s
        np.testing.assert_array_equal(view[:, s, :prompt],
                                      dense[:, s, :prompt])
        np.testing.assert_array_equal(view[0, s, :n], dense[0, s, :n])
        np.testing.assert_allclose(view[:, s, :n], dense[:, s, :n],
                                   rtol=1e-5, atol=1e-6)
