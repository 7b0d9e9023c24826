"""Speculative decoding through the live hop.

The contract under test: after a hop the pre-hop model drafts K tokens per
round and the grown model verifies them in one launch — greedy output is
bit-equal to vanilla greedy decode (drafts only change how many positions a
launch advances), a lossless (LEMON) hop gives 100% first-round acceptance
by construction, sampling is reproducible under a fixed seed, drafting
auto-disables when it can't pay for itself, and a hop abort mid-draft rolls
back with zero dropped sessions. Plus the HopWatchdog cold-start fix.
"""
import jax
import numpy as np
import pytest

from repro.configs.paper_models import BERT_SMALL
from repro.core import init_ligo_params
from repro.core.operators import lemon_operator
from repro.models import init_params
from repro.serving import HopController, HopWatchdog, ServingEngine

TINY = BERT_SMALL.scaled(
    name="spec-tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
    d_head=8, d_ff=64, vocab_size=64, max_seq=96, dtype="float32",
    objective="clm", encoder_only=False, causal=True)
WIDE = TINY.scaled(name="spec-wide", n_heads=8, n_kv_heads=8, d_ff=96)
DEEP = TINY.scaled(name="spec-deep", n_layers=4)

MESHES = [((1,), ("data",)), ((2, 4), ("data", "model"))]
MESH_IDS = ["1dev", "2x4"]


@pytest.fixture(scope="module")
def small_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def _serve(params, cfg2, op, *, spec_k, kv_layout="paged", gen=24,
           temperature=0.0, top_p=1.0, seed=0, hop_at=3, n_req=4,
           fail_at=None, retries=2, mesh=None, second_hop=None):
    eng = ServingEngine(params, TINY, slots=2, prompt_budget=8,
                        gen_budget=gen, kv_layout=kv_layout, spec_k=spec_k,
                        temperature=temperature, top_p=top_p, seed=seed,
                        mesh=mesh, spec_autodisable=False)
    # autodisable off: it reads wall-clock costs (compile noise at test
    # scale), which would make round scheduling — and sampled token
    # streams — nondeterministic; the heuristic is unit-tested directly
    hop = HopController(eng, cfg2, op, cache_mode="auto", fail_at=None,
                        retries=retries, backoff=0.01, background=False)
    hop2 = None
    rng = np.random.RandomState(0)
    reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 4 + i % 4)),
                       max_new=gen) for i in range(n_req)]
    step = 0
    for _ in range(600):
        if not eng.has_work():
            break
        eng.step()
        step += 1
        if step == hop_at:
            hop.begin()
        hop.poll()
        if second_hop is not None and hop.completed and hop2 is None:
            cfg3, op2 = second_hop
            hop2 = HopController(eng, cfg3, op2, cache_mode="auto",
                                 fail_at=fail_at, retries=retries,
                                 backoff=0.01, background=False)
            hop2.begin()
        if hop2 is not None:
            hop2.poll()
    assert hop.completed
    return eng, hop, hop2, reqs


# ---------------------------------------------------------------------------
# Greedy: bit-equal to vanilla, 100% first-round acceptance on a lemon hop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_greedy_spec_bit_equal_to_vanilla(small_params, kv_layout):
    op = lemon_operator(TINY, WIDE)
    _, _, _, vanilla = _serve(small_params, WIDE, op, spec_k=0,
                              kv_layout=kv_layout)
    eng, _, _, spec = _serve(small_params, WIDE, op, spec_k=4,
                             kv_layout=kv_layout)
    assert all(r.status == "done" for r in vanilla + spec)
    assert ([r.tokens for r in vanilla] == [r.tokens for r in spec])
    st = eng.spec_stats
    assert st["rounds"] > 0 and st["accepted"] > 0
    assert st["drafter"] == TINY.name


def test_lemon_hop_first_round_acceptance_is_total(small_params):
    """A lossless hop means drafter and verifier are the same function:
    every draft of the first round must be accepted, and at least 99% of
    all drafts through the hop."""
    op = lemon_operator(TINY, WIDE)
    eng, _, _, reqs = _serve(small_params, WIDE, op, spec_k=4)
    st = eng.spec_stats
    assert st["first_round_acc"] == 1.0
    assert st["drafted"] > 0 and st["accepted"] >= 0.99 * st["drafted"]
    assert all(r.status == "done" for r in reqs)


@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
def test_greedy_spec_through_hop_both_lanes(mesh_factory, small_params,
                                            mesh_def):
    mesh = mesh_factory(*mesh_def)
    op = lemon_operator(TINY, WIDE)
    _, _, _, vanilla = _serve(small_params, WIDE, op, spec_k=0, mesh=mesh)
    eng, _, _, spec = _serve(small_params, WIDE, op, spec_k=3, mesh=mesh)
    assert ([r.tokens for r in vanilla] == [r.tokens for r in spec])
    assert eng.spec_stats["first_round_acc"] == 1.0


def test_drafter_declined_for_windowed_or_mismatched(small_params):
    """adopt_drafter refuses configs whose caches can't take positional
    rollback (ring buffers) or whose vocab differs."""
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=8, spec_k=4)
    win = TINY.scaled(name="spec-win", window=8)
    assert not eng.adopt_drafter(win, small_params, eng.state)
    other = TINY.scaled(name="spec-vocab", vocab_size=32)
    assert not eng.adopt_drafter(other, small_params, eng.state)
    assert not eng.spec_enabled


# ---------------------------------------------------------------------------
# Sampling: reproducible chains, rejection path, vanilla-path sampling
# ---------------------------------------------------------------------------
def test_sampled_spec_reproducible_and_seed_sensitive(small_params):
    op = lemon_operator(TINY, WIDE)
    kw = dict(spec_k=4, temperature=0.8, top_p=0.9, seed=42, gen=16)
    _, _, _, a = _serve(small_params, WIDE, op, **kw)
    _, _, _, b = _serve(small_params, WIDE, op, **kw)
    assert [r.tokens for r in a] == [r.tokens for r in b]
    _, _, _, c = _serve(small_params, WIDE, op, **{**kw, "seed": 7})
    assert [r.tokens for r in a] != [r.tokens for r in c]


def test_sampled_rejection_path_still_terminates(small_params):
    """A *learned* (noisy) operator makes drafter and verifier disagree, so
    rejection + residual resampling actually runs; every request still
    completes and acceptance is partial."""
    op = init_ligo_params(jax.random.PRNGKey(3), TINY, WIDE, noise=0.2)
    eng, _, _, reqs = _serve(small_params, WIDE, op, spec_k=4,
                             temperature=1.0, seed=11, gen=16)
    assert all(r.status == "done" for r in reqs)
    st = eng.spec_stats
    assert 0 < st["accepted"] < st["drafted"]


def test_vanilla_sampling_reproducible(small_params):
    """The non-speculative sampled path rides the same Philox chain."""
    def run(seed):
        eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                            gen_budget=8, temperature=0.9, top_p=0.8,
                            seed=seed)
        rng = np.random.RandomState(0)
        reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 5)),
                           max_new=8) for _ in range(3)]
        while eng.has_work():
            eng.step()
        return [r.tokens for r in reqs]

    assert run(5) == run(5)
    assert run(5) != run(6)


# ---------------------------------------------------------------------------
# Telemetry + auto-disable
# ---------------------------------------------------------------------------
def test_auto_disable_when_drafting_cannot_pay(small_params):
    """Feed the telemetry three rounds where drafting costs more than it
    saves; the engine must disable drafting (sticky) and say so."""
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=8, spec_k=4)
    assert eng.adopt_drafter(TINY, small_params, eng.state)
    for _ in range(3):
        # 0 of K accepted, draft as slow as verify: est < 1 guaranteed
        eng._spec_telemetry(2, 0, t_draft=0.04, t_verify=0.01)
    assert not eng.spec_enabled
    assert "est speedup" in eng.spec_stats["disabled"]
    # sticky: a later healthy round cannot resurrect it via _spec_ready
    assert not eng._spec_ready([])


# ---------------------------------------------------------------------------
# Chaos: hop abort mid-draft
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fail_at", ["grow", "cache-grow", "swap"])
def test_hop_abort_mid_draft_drops_nothing(small_params, fail_at):
    """First hop succeeds and drafting goes live; a second hop then fails at
    each stage *while rounds are speculative*. The abort must roll back with
    zero dropped sessions, keep the resident drafter drafting, and leave the
    page allocator consistent."""
    op1 = lemon_operator(TINY, WIDE)
    cfg3 = WIDE.scaled(name="spec-wider", n_heads=16, n_kv_heads=16,
                       d_ff=128)
    op2 = lemon_operator(WIDE, cfg3)
    eng, hop, hop2, reqs = _serve(
        small_params, WIDE, op1, spec_k=4, gen=32, retries=0,
        fail_at=fail_at, second_hop=(cfg3, op2))
    assert hop.completed
    assert hop2 is not None and hop2.failed      # retries=0: abort is final
    assert eng.cfg.name == WIDE.name             # rolled back to hop-1 model
    assert eng.spec_stats["rounds"] > 0          # drafting really ran
    assert all(r.status == "done" for r in reqs)
    assert eng.counts()["dropped"] == 0
    # allocator consistency after the abort: everything released, no leak
    a = eng.alloc
    assert a is not None
    assert len(a.free) == a.n_blocks and (a.table == -1).all()
    assert (a.allocated == 0).all() and (a.reserved == 0).all()


def test_hop_retry_succeeds_while_drafting(small_params):
    """Same abort, but with a retry budget: the second hop recovers, the
    engine lands on the final model and the drafter is the mid model."""
    op1 = lemon_operator(TINY, WIDE)
    cfg3 = WIDE.scaled(name="spec-wider", n_heads=16, n_kv_heads=16,
                       d_ff=128)
    op2 = lemon_operator(WIDE, cfg3)
    eng, hop, hop2, reqs = _serve(
        small_params, WIDE, op1, spec_k=4, gen=32, retries=2,
        fail_at="swap", second_hop=(cfg3, op2))
    assert hop2 is not None and hop2.completed and hop2.attempts == 2
    assert eng.cfg.name == cfg3.name
    assert eng.spec_stats["drafter"] == WIDE.name
    assert all(r.status == "done" for r in reqs)
    assert eng.counts()["dropped"] == 0


# ---------------------------------------------------------------------------
# Depth-replay cache fast path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_depth_replay_matches_reprefill(small_params, kv_layout):
    """A stack-pattern depth-append operator (identity width + identity-
    prefix depth) replays only the new layers from the preserved residual
    stream; served tokens must match the re-prefill oracle exactly, and
    'auto' must pick the replay path."""
    op = init_ligo_params(jax.random.PRNGKey(7), TINY, DEEP,
                          depth_init="stack", noise=0.0)

    def run(mode):
        eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                            gen_budget=24, kv_layout=kv_layout)
        hop = HopController(eng, DEEP, op, cache_mode=mode,
                            background=False)
        rng = np.random.RandomState(0)
        reqs = [eng.submit(list(rng.randint(0, TINY.vocab_size, 4 + i % 4)),
                           max_new=24) for i in range(4)]
        step = 0
        while eng.has_work():
            eng.step()
            step += 1
            if step == 3:
                hop.begin()
            hop.poll()
        assert hop.completed and all(r.status == "done" for r in reqs)
        return [r.tokens for r in reqs], hop.cache_path

    replay, mode_r = run("replay")
    oracle, mode_o = run("reprefill")
    auto, mode_a = run("auto")
    assert (mode_r, mode_o, mode_a) == ("replay", "reprefill", "replay")
    assert replay == oracle == auto


def test_forced_replay_rejects_non_depth_operator(small_params):
    """cache_mode='replay' with a width operator must fail the hop cleanly
    (rollback, engine keeps serving), not silently fall back."""
    op = lemon_operator(TINY, WIDE)
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=8)
    hop = HopController(eng, WIDE, op, cache_mode="replay", retries=0,
                        background=False)
    reqs = [eng.submit([1, 2, 3], max_new=8)]
    step = 0
    while eng.has_work():
        eng.step()
        step += 1
        if step == 2:
            hop.begin()
        hop.poll()
    assert hop.failed and eng.cfg.name == TINY.name
    assert all(r.status == "done" for r in reqs)


# ---------------------------------------------------------------------------
# HopWatchdog cold start + warm()
# ---------------------------------------------------------------------------
def test_watchdog_cold_budget_is_timeout():
    assert HopWatchdog(timeout=3.0).budget() == 3.0


def test_watchdog_seed_sets_floor_and_ewma():
    wd = HopWatchdog(timeout=120.0)
    wd.seed(2.0)
    assert wd.ewma == 2.0 and wd.floor == 2.0
    assert wd.budget() == pytest.approx(wd.mult * 2.0)
    # floor survives a timeout tighter than the measured first grow
    wd2 = HopWatchdog(timeout=0.001)
    wd2.seed(2.0)
    assert wd2.budget() >= 2.0
    # seeding never shrinks an existing floor, nor overwrites observations
    wd2.seed(1.0)
    assert wd2.floor == 2.0 and wd2.ewma == 2.0
    wd2.observe(4.0)
    wd2.seed(9.0)                      # floor may rise...
    assert wd2.floor == 9.0
    assert wd2.ewma == pytest.approx(3.0)   # ...but the EWMA is real data


def test_watchdog_config_floor_plumbs_through():
    eng_like = HopWatchdog(timeout=0.5, floor=7.0)
    assert eng_like.budget() == 7.0


def test_warm_seeds_watchdog_and_survives_tight_timeout(small_params):
    """The cold-start bug in one test: a timeout far below the real first
    grow cost would previously abort the first hop; warm() measures the
    grow at engine start and seeds the watchdog, so the hop survives."""
    op = lemon_operator(TINY, WIDE)
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=8)
    hop = HopController(eng, WIDE, op, timeout=1e-6, retries=0,
                        background=False)
    dt = hop.warm()
    assert dt > 0 and hop.watchdog.ewma is not None
    assert hop.watchdog.budget() >= dt
    reqs = [eng.submit([1, 2, 3], max_new=8)]
    step = 0
    while eng.has_work():
        eng.step()
        step += 1
        if step == 2:
            hop.begin()
        hop.poll()
    assert hop.completed                 # would be a watchdog abort cold
    assert all(r.status == "done" for r in reqs)


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_warm_compiles_the_reprefill(small_params, kv_layout):
    """warm() also compiles the grown model's re-prefill, so a hop that
    meets live sessions and re-prefills them compiles nothing inside its
    cache-grow stage."""
    from repro import obs
    wide = WIDE.scaled(name=f"spec-wide-warm-{kv_layout}")   # a cold jit
    eng = ServingEngine(small_params, TINY, slots=2, prompt_budget=8,
                        gen_budget=8, kv_layout=kv_layout)
    hop = HopController(eng, wide, lemon_operator(TINY, wide),
                        cache_mode="reprefill", background=False)
    hop.warm()
    compiles = obs.counter_group("jax.compiles")
    before = compiles.get("hop.cache-grow")
    reqs = [eng.submit([1, 2, 3], max_new=8), eng.submit([4, 5], max_new=8)]
    eng.step()
    eng.step()
    hop.begin()
    assert len(eng.live) == 2 and hop.poll()
    assert hop.completed and hop.cache_path == "reprefill"
    assert compiles.get("hop.cache-grow") == before
    eng.run()
    assert all(r.status == "done" for r in reqs)
