"""Serving engine + zero-downtime live growth.

Covers the KV-cache growth rule (grown-cache decode vs full re-prefill
decode, per method: bit-exact for LEMON-style lossless expanders, ≤1e-5 for
learned LiGO — whose migration path is re-prefill), fault injection at every
hop stage (rollback leaves the engine decoding old weights, zero dropped
sessions, retry succeeds), admission control, and ``serve --ckpt`` restore.

Mesh-parametrized cases run fully on the forced-8-virtual-device CI lane
(REPRO_FORCE_HOST_DEVICES=8) and degrade to the 1-device cases elsewhere.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_models import BERT_SMALL
from repro.core import apply_ligo, init_ligo_params
from repro.core.grow_cache import (CacheGrowthError, can_grow_cache,
                                   grow_decode_state, is_lossless_operator)
from repro.core.operators import lemon_operator, net2net_operator
from repro.models import init_params
from repro.serving import HopController, HopWatchdog, ServingEngine
from repro.serving.engine import make_serving_fns

TINY = BERT_SMALL.scaled(
    name="srv-tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="float32",
    objective="clm", encoder_only=False, causal=True)
# lemon-compatible target: width-only (heads + ffn), MHA on both sides
WIDE = TINY.scaled(name="srv-wide", n_heads=8, n_kv_heads=8, d_ff=96)
# general LiGO target (depth + width): cache migration must re-prefill
BIG = TINY.scaled(name="srv-big", n_layers=4, d_model=48, d_head=12,
                  d_ff=96)

MESHES = [((1,), ("data",)), ((2, 4), ("data", "model"))]
MESH_IDS = ["1dev", "2x4"]


@pytest.fixture(scope="module")
def small_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _fill_engine(params, cfg, *, n_req=4, gen=12, mesh=None, slots=2,
                 queue_capacity=64, cls=ServingEngine, **engine_kw):
    eng = cls(params, cfg, slots=slots, prompt_budget=8, gen_budget=gen,
              queue_capacity=queue_capacity, mesh=mesh, **engine_kw)
    rng = np.random.RandomState(0)
    for i in range(n_req):
        eng.submit(list(rng.randint(0, cfg.vocab_size, 4 + i % 4)),
                   max_new=gen)
    return eng


def _operator(method, cfg2):
    if method == "lemon":
        return lemon_operator(TINY, cfg2)
    return init_ligo_params(jax.random.PRNGKey(7), TINY, cfg2)


# ---------------------------------------------------------------------------
# Lossless oracle + cache growth rule
# ---------------------------------------------------------------------------
def test_lemon_operator_is_bitwise_function_preserving(small_params):
    """The exactness oracle: zero-pad growth changes no logit bit."""
    op = lemon_operator(TINY, WIDE)
    assert is_lossless_operator(op, TINY, WIDE)
    big = apply_ligo(op, small_params, TINY, WIDE)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              TINY.vocab_size)
    from repro.models.model import prefill
    lg1, _ = prefill(small_params, TINY, {"tokens": toks}, max_len=16)
    lg2, _ = prefill(big, WIDE, {"tokens": toks}, max_len=16)
    assert np.array_equal(np.asarray(lg1), np.asarray(lg2))


def test_lemon_operator_rejects_lossy_targets():
    with pytest.raises(ValueError):                  # d_model changes norms
        lemon_operator(TINY, TINY.scaled(name="w", d_model=48, d_head=12))
    with pytest.raises(ValueError):                  # depth is never lossless
        lemon_operator(TINY, TINY.scaled(name="d", n_layers=4))
    gqa = TINY.scaled(name="g", n_heads=8, n_kv_heads=4, d_ff=96)
    with pytest.raises(ValueError):                  # GQA wo averages heads
        lemon_operator(TINY, gqa)


def test_lossless_detector_rejects_learned_and_copy_operators():
    assert not is_lossless_operator(
        init_ligo_params(jax.random.PRNGKey(0), TINY, WIDE), TINY, WIDE)
    assert not is_lossless_operator(
        net2net_operator(jax.random.PRNGKey(0), TINY, WIDE), TINY, WIDE)
    assert not is_lossless_operator(_operator("lemon", WIDE), TINY, BIG)


def test_grow_decode_state_refuses_non_attn_and_depth():
    op = init_ligo_params(jax.random.PRNGKey(0), TINY, BIG)
    eng = _fill_engine(init_params(TINY, jax.random.PRNGKey(0)), TINY)
    eng.step()
    with pytest.raises(CacheGrowthError):            # non-identity depth
        grow_decode_state(eng.state, op, TINY, BIG)
    assert not can_grow_cache(TINY, TINY.scaled(name="win", window=8))


# ---------------------------------------------------------------------------
# Grown-cache decode vs full re-prefill decode (the acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
@pytest.mark.parametrize("method", ["lemon", "ligo"])
def test_cache_migration_matches_reprefill_decode(mesh_factory, small_params,
                                                  method, mesh_def):
    """Migrate a live engine's decode state with the method's cache path
    (in-place growth for lossless lemon, re-prefill for learned LiGO) and
    decode. Two oracles:

    - lemon only, BITWISE on a single-device mesh: the small model's
      continued decode — losslessness means the hop changes no served
      logit bit. On a multi-device mesh the wide model's contractions are
      partitioned over the model axis, so its f32 sums reassociate
      differently than the small model's; there the same oracle holds at
      last-ulp tolerance instead;
    - both methods, ≤1e-5: the full re-prefill decode under the grown
      weights. (Even a lossless grown cache is not bit-identical to a
      re-prefilled one: the two caches come from different prefill shapes,
      so XLA reassociates the same f32 sums differently.)
    """
    mesh = mesh_factory(*mesh_def)
    cfg2 = WIDE if method == "lemon" else BIG
    op = _operator(method, cfg2)
    big = apply_ligo(op, small_params, TINY, cfg2)

    eng = _fill_engine(small_params, TINY, mesh=mesh)
    for _ in range(3):
        eng.step()                                   # sessions mid-flight
    assert eng.live

    if method == "lemon":
        migrated = grow_decode_state(eng.state, op, TINY, cfg2, mesh=mesh)
    else:
        migrated = eng.reprefill_state(big, cfg2)
    oracle = eng.reprefill_state(big, cfg2)

    _, decode, _ = make_serving_fns(cfg2, eng.max_len)
    _, decode_small, _ = make_serving_fns(TINY, eng.max_len)
    live = [i for i, r in enumerate(eng.slot_req) if r is not None]
    last = np.zeros((eng.slots, 1), np.int32)
    for i in live:
        last[i, 0] = eng.slot_req[i].tokens[-1]
    toks = jnp.asarray(last)
    sa, sb, ss = migrated, oracle, eng.state
    for _ in range(4):
        la, sa, pa = decode(big, sa, toks)
        lb, sb, _ = decode(big, sb, toks)
        ls, ss, _ = decode_small(small_params, ss, toks)
        la, lb, ls = (np.asarray(x) for x in (la, lb, ls))
        # the program's greedy pick is the host's argmax of its logits
        np.testing.assert_array_equal(np.asarray(pa), np.argmax(la, -1))
        if method == "lemon":
            if math.prod(mesh_def[0]) == 1:
                assert np.array_equal(la[live], ls[live])
            else:
                np.testing.assert_allclose(la[live], ls[live], rtol=2e-6,
                                           atol=2e-7)
        np.testing.assert_allclose(la[live], lb[live], rtol=1e-5,
                                   atol=1e-5)
        toks = jnp.asarray(np.argmax(la, -1)[:, None])


# ---------------------------------------------------------------------------
# The live hop end-to-end + chaos envelope
# ---------------------------------------------------------------------------
def _run_with_hop(params, cfg2, op, *, fail_at=None, retries=2,
                  background=False, timeout=120.0, hop_at=2, gen=16,
                  cache_mode="auto", mesh=None, **engine_kw):
    eng = _fill_engine(params, TINY, n_req=4, gen=gen, mesh=mesh,
                       **engine_kw)
    hop = HopController(eng, cfg2, op, cache_mode=cache_mode,
                        fail_at=fail_at, retries=retries, backoff=0.01,
                        timeout=timeout, background=background)

    def on_step(e):
        if e.decode_steps >= hop_at and hop.attempts == 0:
            hop.begin()
        if hop.attempts:
            hop.poll()

    eng.run(on_step=on_step)
    while not hop.poll():
        pass
    return eng, hop


@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
def test_live_hop_lossless_end_to_end(mesh_factory, small_params, mesh_def):
    """A lemon hop mid-serve takes the in-place cache path and every
    admitted request completes with finite outputs."""
    mesh = mesh_factory(*mesh_def)
    op = lemon_operator(TINY, WIDE)
    eng, hop = _run_with_hop(small_params, WIDE, op, mesh=mesh)
    assert hop.completed and hop.cache_path == "grow"
    c = eng.counts()
    assert c["done"] == 4 and c["dropped"] == 0
    assert eng.cfg.name == WIDE.name
    assert all(len(r.tokens) == r.max_new for r in eng.requests)


@pytest.mark.parametrize("stage", ["grow", "cache-grow", "swap", "hang"])
def test_hop_chaos_rolls_back_and_retry_succeeds(small_params, stage):
    """A failure injected at every hop stage rolls back (engine keeps
    decoding old weights, zero dropped sessions) and the retry lands."""
    op = init_ligo_params(jax.random.PRNGKey(7), TINY, BIG)
    # pre-warm the (memoised) plan executor so the retry's grow is a cached
    # apply — the hang case's tight watchdog must abort the wedged thread,
    # not a cold compile
    from repro.core.plan import plan_for
    jax.block_until_ready(
        plan_for(TINY, BIG, small_params).executor(mesh=None)(
            op, small_params))
    eng, hop = _run_with_hop(
        small_params, BIG, op, fail_at=stage,
        background=(stage == "hang"),
        timeout=(0.5 if stage == "hang" else 120.0))
    assert hop.completed, stage
    assert hop.attempts == 2                         # failed once, then clean
    c = eng.counts()
    assert c["done"] == 4 and c["dropped"] == 0, (stage, c)
    assert all(len(r.tokens) == r.max_new for r in eng.requests)


class _HostPicks(ServingEngine):
    """The oracle for the program's picks: every logits row goes to the
    host, and greedy decoding takes NumPy's argmax of it there."""

    def _rows(self, out):
        logits = np.asarray(out[0])
        return logits.reshape(-1, logits.shape[-1])

    def _pick_token(self, req, row):
        if self.temperature <= 0:
            return int(np.argmax(row))
        return super()._pick_token(req, row)


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_picks_match_the_host_path_across_a_hop(small_params, kv_layout,
                                                temperature):
    """Through a LiGO hop (re-prefill migration), the engine serves the
    tokens of the host path: greedy, the program's pick is the host's
    argmax, and every token counts under ``serve.picks["device"]``;
    sampled, the logits rows still reach the host, the Philox stream is
    the same, and every token counts under ``"host"``."""
    from repro.obs import FLIGHT
    op = _operator("ligo", BIG)
    kw = dict(kv_layout=kv_layout, temperature=temperature, seed=3)
    oracle, _ = _run_with_hop(small_params, BIG, op, cls=_HostPicks, **kw)
    picks = obs.counter_group("serve.picks")
    before = {k: picks[k] for k in ("device", "host")}
    eng, hop = _run_with_hop(small_params, BIG, op, **kw)
    assert hop.completed and hop.cache_path == "reprefill"
    assert eng.kv_layout == kv_layout
    assert all(r.status == "done" for r in eng.requests)
    assert ([r.tokens for r in eng.requests]
            == [r.tokens for r in oracle.requests])
    served = sum(len(r.tokens) for r in eng.requests)
    where = "device" if temperature <= 0 else "host"
    assert {k: picks[k] - before[k] for k in before} == {
        k: served if k == where else 0 for k in before}
    sample = FLIGHT.events(type="span", prefix="serve.sample")[-1]
    assert sample["attrs"]["picked"] == where


def test_hop_gives_up_and_engine_survives_on_old_weights(small_params):
    """Retries exhausted: the hop reports failure and the engine finishes
    every request on the old architecture — rollback is total."""
    op = init_ligo_params(jax.random.PRNGKey(7), TINY, BIG)
    eng, hop = _run_with_hop(small_params, BIG, op, fail_at="grow",
                             retries=0)
    assert hop.failed and not hop.completed
    assert eng.cfg.name == TINY.name
    c = eng.counts()
    assert c["done"] == 4 and c["dropped"] == 0


def test_background_grow_overlaps_decoding(small_params):
    """Background mode: the engine keeps producing tokens while the grow
    thread runs, and the swap still lands between decode steps."""
    op = lemon_operator(TINY, WIDE)
    eng, hop = _run_with_hop(small_params, WIDE, op, background=True,
                             gen=24)
    assert hop.completed
    assert eng.counts()["done"] == 4
    assert hop.swap_at_step is not None


def test_admission_control(small_params):
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=4, queue_capacity=3)
    over = eng.submit(list(range(20)), max_new=4)    # prompt > budget
    assert over.status == "rejected"
    reqs = [eng.submit([1, 2, 3], max_new=4) for _ in range(5)]
    assert sum(r.status == "rejected" for r in reqs) == 2   # queue cap 3
    eng.run()
    c = eng.counts()
    assert c["done"] == 3 and c["rejected"] == 3 and c["dropped"] == 0


def test_watchdog_budget_tracks_observed_hops():
    wd = HopWatchdog(timeout=100.0, mult=5.0)
    assert wd.budget() == 100.0                      # cold: hard timeout
    wd.observe(0.2)
    assert wd.budget() == pytest.approx(1.0)         # warmed: 5x EWMA
    wd.observe(100.0)                                # ewma -> 50.1
    assert wd.budget() == 100.0                      # capped at hard timeout


# ---------------------------------------------------------------------------
# serve.py drivers: --ckpt restore, --live-grow-at CLI
# ---------------------------------------------------------------------------
def test_serve_ckpt_restore(tmp_path, monkeypatch, capsys):
    """serve --ckpt restores the newest trained checkpoint (trainer layout,
    optimizer state ignored) sharded via params_pspecs, then serves it."""
    import sys
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, smoke_config
    from repro.launch import serve
    cfg = smoke_config(get_config("llama3-8b"))
    params = init_params(cfg, jax.random.PRNGKey(3))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, {"params": params, "opt": {"step": np.zeros((), np.int32)}},
             {"arch": cfg.name}, block=True)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3-8b", "--smoke", "--ckpt", str(tmp_path),
        "--batch", "1", "--prompt-len", "8", "--gen", "3"])
    serve.main()
    out = capsys.readouterr().out
    assert "restored step-5 checkpoint" in out
    assert "tok/s" in out


def test_serve_ckpt_missing_errors(tmp_path, monkeypatch):
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3-8b", "--smoke",
        "--ckpt", str(tmp_path / "nope")])
    with pytest.raises(SystemExit, match="no checkpoint"):
        serve.main()


def test_serve_live_grow_cli(monkeypatch, capsys):
    """The CLI live path: a chaos-injected hop rolls back, retries, and the
    run reports zero drops and throughput through the hop."""
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "llama3-8b", "--smoke", "--live-grow-at", "2",
        "--fail-at-hop", "cache-grow", "--hop-sync", "--grow-to", "2x",
        "--batch", "2", "--prompt-len", "8", "--gen", "6"])
    serve.main()
    out = capsys.readouterr().out
    assert "rolled back" in out
    assert "hop complete" in out
    assert "0 dropped" in out
    assert "tok/s" in out and "p99" in out


def test_serve_live_grow_cli_gives_up_nonzero(capsys):
    """A hop that fails with no retries left makes the driver exit
    non-zero, after every request was still served by the old model."""
    from repro.launch import serve
    with pytest.raises(SystemExit, match="gave up after 1 attempts"):
        serve.main([
            "--arch", "llama3-8b", "--smoke", "--live-grow-at", "2",
            "--fail-at-hop", "grow", "--hop-retries", "0", "--hop-sync",
            "--grow-to", "2x", "--batch", "2", "--prompt-len", "8",
            "--gen", "6"])
    out = capsys.readouterr().out
    assert "FAILED (gave up)" in out and "0 dropped" in out
