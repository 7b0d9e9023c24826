"""repro.autogrow: the adaptive growth controller + the elastic LiGO phase.

Covers the three legs of the subsystem: (1) telemetry — ring-buffer signal
stream, snapshot/restore determinism; (2) policies — step_budget reproduces
the static schedule bit-for-bit, loss_plateau / rpf_decay fire at the
plateau of a synthetic decaying-loss stream (the acceptance case), probe
picks the best candidate operator; (3) the elastic LiGO phase — a kill
mid-phase resumes from the phase checkpoint (never the stage boundary) and
reproduces the uninterrupted operator bit-for-bit, unsharded and (on the
forced-8-device lane) across meshes. Plus the clear-error paths for
optimizer state that predates grow_state.
"""
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_trees_close_normalized

from repro.autogrow import PolicySpec, Telemetry, make_policy, probe_methods
from repro.checkpoint import CheckpointManager
from repro.checkpoint.io import save_step
from repro.configs.paper_models import BERT_SMALL
from repro.core import grow, init_ligo_params, train_ligo
from repro.data import batch_for_step
from repro.optim import adamw_init, sgd_init
from repro.trajectory import (GrowthSpec, Stage, TrajectoryConfig,
                              TrajectoryRunner)
from repro.trajectory.runner import LIGO_PHASE_DIR
from repro.training import init_train_state, make_train_step
from repro.configs.base import TrainConfig

T0 = BERT_SMALL.scaled(name="ag0", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=4, d_head=8, d_ff=64, vocab_size=64,
                       max_seq=64, dtype="float32", objective="clm",
                       encoder_only=False, causal=True)
T1 = T0.scaled(name="ag1", n_layers=3, d_model=48, n_heads=6, n_kv_heads=6,
               d_ff=96)


def _decaying_stream(tau=15.0, plateau=1.0, amp=1.0):
    t = 0
    while True:
        yield plateau + amp * math.exp(-t / tau)
        t += 1


def _pretrained_small(steps=8):
    params, opt = init_train_state(T0, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        T0, TrainConfig(steps=steps, warmup_steps=2, lr=1e-3)))
    for i in range(steps):
        b = {k: jnp.asarray(v)
             for k, v in batch_for_step(T0, i, 4, 16, seed=0).items()}
        params, opt, _ = step(params, opt, b, jnp.asarray(i))
    return params, opt


def _ligo_batches(seed=5):
    t = 0
    while True:
        yield {k: jnp.asarray(v)
               for k, v in batch_for_step(T0, t, 4, 16, seed=seed).items()}
        t += 1


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------
def test_telemetry_ring_and_signals():
    tele = Telemetry(window=8, flops_per_step=1e9, tokens_per_step=64)
    stream = _decaying_stream()
    for t in range(30):
        tele.record(t, next(stream))
    assert len(tele) == 8 and tele.full
    assert tele.total_steps == 30
    assert tele.cum_flops == pytest.approx(30e9)
    assert tele.cum_tokens == pytest.approx(30 * 64)
    # still improving at t=30 of a tau=15 decay: positive improvement and
    # positive return-per-FLOP, below its early peak
    assert tele.improvement() > 0
    assert tele.rpf() > 0
    assert tele.peak_rpf >= tele.rpf()
    assert 0 < tele.rpf_decay() <= 1.0


def test_telemetry_snapshot_roundtrip_preserves_decisions():
    spec = PolicySpec(kind="loss_plateau", max_steps=500, min_steps=10,
                      window=8, tol=2e-3)
    pol = make_policy(spec)
    a = pol.telemetry(flops_per_step=1e9)
    stream = _decaying_stream()
    for t in range(40):
        a.record(t, next(stream))
    b = Telemetry.restore(a.snapshot(), flops_per_step=1e9)
    assert b.improvement() == a.improvement()
    assert b.rpf() == a.rpf()
    assert b.peak_rpf == a.peak_rpf
    # identical decision sequence when both streams keep recording
    for t in range(40, 300):
        loss = next(_decaying_stream())  # same analytic value at each t
        loss = 1.0 + math.exp(-t / 15.0)
        a.record(t, loss)
        b.record(t, loss)
        assert pol.should_grow(t, a) == pol.should_grow(t, b)


# ---------------------------------------------------------------------------
# Policies on the synthetic decaying-loss stream (the acceptance case)
# ---------------------------------------------------------------------------
def test_loss_plateau_fires_at_the_plateau():
    """loss(t) = 1 + e^{-t/15}: the relative EMA improvement over a window
    W falls below tol ≈ when e^{-t/15}·(1 - e^{-W/15}) / ema < tol·ema —
    solvable analytically; the policy must fire within a few steps of it."""
    spec = PolicySpec(kind="loss_plateau", max_steps=10_000, min_steps=10,
                      window=8, tol=2e-3, ema_halflife=8)
    pol = make_policy(spec)
    tele = pol.telemetry()
    fired = None
    stream = _decaying_stream(tau=15.0)
    for t in range(10_000):
        tele.record(t, next(stream))
        if pol.should_grow(t, tele):
            fired = t
            break
    assert fired is not None, "plateau policy never fired on a decaying stream"
    # exp decay amp/(1+amp·e^{-t/τ}) improvement: tol crossing is near
    # τ·ln(amp·(1 - e^{-W/τ}) / tol) ≈ 15·ln(0.44/2e-3) ≈ 81; EMA smoothing
    # and the windowed difference shift it late by O(window + halflife)
    analytic = 15.0 * math.log((1 - math.exp(-8 / 15.0)) / 2e-3)
    assert analytic < fired < analytic + 3 * (spec.window +
                                              spec.ema_halflife), \
        (fired, analytic)
    assert not pol.should_grow(5, pol.telemetry())  # min_steps guard


def test_rpf_decay_fires_on_decay_not_on_steady_progress():
    spec = PolicySpec(kind="rpf_decay", max_steps=10_000, min_steps=10,
                      window=8, decay=0.25)
    pol = make_policy(spec)
    tele = pol.telemetry(flops_per_step=1e9)
    fired = None
    stream = _decaying_stream(tau=15.0)
    for t in range(10_000):
        tele.record(t, next(stream))
        if pol.should_grow(t, tele):
            fired = t
            break
    # rpf halves every τ·ln2 ≈ 10.4 steps; 1/4 of peak is ~2 halvings after
    # the ring fills → fires early, and certainly before the plateau tail
    assert fired is not None and 10 <= fired < 80, fired

    tele_lin = pol.telemetry(flops_per_step=1e9)
    for t in range(300):                        # constant-slope improvement
        tele_lin.record(t, 10.0 - 1e-3 * t)
        assert not pol.should_grow(t, tele_lin), t


def test_step_budget_policy_reproduces_static_schedule_bit_for_bit():
    """steps='auto' + a step_budget policy is the identity controller: the
    run must equal the static schedule exactly."""
    static = TrajectoryConfig(stages=(
        Stage(T0, 4),
        Stage(T1, 3, GrowthSpec(method="stackbert"))),
        batch=4, seq=16, checkpoint_every=10)
    auto = TrajectoryConfig(stages=(
        Stage(T0, None, policy=PolicySpec(kind="step_budget", max_steps=4)),
        Stage(T1, 3, GrowthSpec(method="stackbert"))),
        batch=4, seq=16, checkpoint_every=10)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        r_s = TrajectoryRunner(static, ckpt_dir=d1, verbose=False).run()
        r_a = TrajectoryRunner(auto, ckpt_dir=d2, verbose=False).run()
    assert r_s["global_step"] == r_a["global_step"] == 7
    assert [h[2] for h in r_s["history"]] == [h[2] for h in r_a["history"]]
    for a, b in zip(jax.tree.leaves(r_s["params"]),
                    jax.tree.leaves(r_a["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_probe_picks_the_best_candidate():
    """LAG-style probe: a warm stackbert growth of a genuinely pretrained
    source must out-probe a cold random re-init of the big model."""
    params, opt = _pretrained_small(steps=80)
    spec = PolicySpec(kind="probe", max_steps=100,
                      probe_candidates=("stackbert", "random"),
                      probe_steps=6, probe_ligo_steps=0)
    best, scores = probe_methods(params, opt, T0, T1, spec,
                                 lr=1e-3, batch=4, seq=16, seed=0)
    assert set(scores) == {"stackbert", "random"}
    assert best == "stackbert", scores
    assert scores["stackbert"] < scores["random"]


# ---------------------------------------------------------------------------
# Elastic LiGO phase
# ---------------------------------------------------------------------------
def test_ligo_phase_kill_resume_bit_equal():
    """A phase killed at a chunk boundary resumes from the phase checkpoint
    and reproduces the uninterrupted operator bit-for-bit (same chunked
    program, carry round-trips exactly through the npz checkpoint)."""
    sp = _pretrained_small()[0]
    lg = init_ligo_params(jax.random.PRNGKey(1), T0, T1)
    op_full, losses_full = train_ligo(lg, sp, T0, T1, _ligo_batches(),
                                      steps=6, scan_chunk=2)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        with pytest.raises(RuntimeError, match="injected LiGO-phase"):
            train_ligo(lg, sp, T0, T1, _ligo_batches(), steps=6,
                       scan_chunk=2, phase_ckpt=mgr, fail_at=2)
        meta = mgr.latest_meta()
        assert meta["phase_step"] == 2          # died after chunk 1's save
        op_res, losses_res = train_ligo(lg, sp, T0, T1, _ligo_batches(),
                                        steps=6, scan_chunk=2,
                                        phase_ckpt=mgr)
    np.testing.assert_allclose(losses_res, losses_full, rtol=0, atol=0)
    for a, b in zip(jax.tree.leaves(op_res), jax.tree.leaves(op_full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ligo_phase_stale_checkpoint_ignored():
    """A phase directory left by a different hop (other budget/config/stage)
    must not be resumed into this phase — fresh start, same result."""
    sp = _pretrained_small()[0]
    lg = init_ligo_params(jax.random.PRNGKey(1), T0, T1)
    want, _ = train_ligo(lg, sp, T0, T1, _ligo_batches(), steps=4,
                         scan_chunk=2)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        # a valid-looking carry from a DIFFERENT phase (other step budget)
        with pytest.raises(RuntimeError):
            train_ligo(lg, sp, T0, T1, _ligo_batches(), steps=6,
                       scan_chunk=2, phase_ckpt=mgr, fail_at=2)
        got, _ = train_ligo(lg, sp, T0, T1, _ligo_batches(), steps=4,
                            scan_chunk=2, phase_ckpt=CheckpointManager(d))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Runner integration
# ---------------------------------------------------------------------------
AUTO_TRAJ = TrajectoryConfig(stages=(
    Stage(T0, 4),
    Stage(T1, None, GrowthSpec(method="ligo", ligo_steps=4,
                               ligo_scan_chunk=2),
          policy=PolicySpec(kind="loss_plateau", max_steps=12, min_steps=2,
                            window=3, tol=5e-3, ema_halflife=2))),
    batch=4, seq=16, checkpoint_every=3)


def test_runner_auto_stage_ends_at_plateau_before_cap():
    with tempfile.TemporaryDirectory() as d:
        r = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, verbose=False).run()
    assert r["status"] == "done"
    assert r["decisions"], "no autogrow decision recorded"
    dec = r["decisions"][-1]
    assert dec["kind"] == "loss_plateau"
    assert 2 <= dec["stage_step"] < 12          # fired before the hard cap
    assert r["stage_step"] == dec["stage_step"]


def test_runner_auto_stage_kill_resume_same_decision():
    """Pause mid-auto-stage: the telemetry tail rides the checkpoint meta,
    so the resumed run fires the policy at the same step with the same
    final state as the uninterrupted run.

    The step at which the plateau fires depends on the random init, so the
    pause point is taken from the uninterrupted run: one step before the
    decision, which is inside the auto stage because the policy cannot fire
    before its ``min_steps``."""
    with tempfile.TemporaryDirectory() as d:
        full = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, verbose=False).run()
    stage0_steps = AUTO_TRAJ.stages[0].steps
    pause_at = full["decisions"][-1]["global_step"] - 1
    assert pause_at > stage0_steps, "decision fired before the auto stage"
    with tempfile.TemporaryDirectory() as d:
        r1 = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d,
                              verbose=False).run(max_steps=pause_at)
        assert r1["status"] == "paused"
        meta = CheckpointManager(d).latest_meta()
        assert meta["stage"] == 1 and "autogrow" in meta
        assert meta["autogrow"]["ring"], "telemetry tail not checkpointed"
        r2 = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, verbose=False).run()
    assert r2["status"] == full["status"] == "done"
    assert r2["decisions"][-1]["stage_step"] == \
        full["decisions"][-1]["stage_step"]
    assert r2["global_step"] == full["global_step"]
    assert_trees_close_normalized(r2["params"], full["params"], rel=1e-6)


def _runner_kill_resume_mid_ligo(mesh, resume_mesh):
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(RuntimeError, match="injected LiGO-phase"):
            TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, mesh=mesh,
                             verbose=False, ligo_fail_at=2).run()
        phase_dir = os.path.join(d, LIGO_PHASE_DIR)
        phase_meta = CheckpointManager(phase_dir).latest_meta()
        assert phase_meta is not None and phase_meta["phase_step"] == 2
        assert phase_meta["stage"] == 1
        # the main stream is still at the stage-0 boundary...
        assert CheckpointManager(d).latest_meta()["stage"] == 0
        # ...but the resume must continue the phase from step 2, not redo it
        r2 = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, mesh=resume_mesh,
                              verbose=False).run()
        assert r2["status"] == "done"
        assert not os.path.isdir(phase_dir), \
            "phase checkpoints must be cleaned up after the hop lands"
    return r2


def test_runner_mid_ligo_kill_resumes_from_phase_checkpoint():
    r2 = _runner_kill_resume_mid_ligo(None, None)
    with tempfile.TemporaryDirectory() as d:
        full = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, verbose=False).run()
    # same phase chunks from the restored carry → identical final operator
    # → identical grown params and training tail
    for a, b in zip(jax.tree.leaves(r2["params"]),
                    jax.tree.leaves(full["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the resumed process's history holds only its own steps — compare the
    # full stage-1 leg, which both runs train end-to-end from the (bit-
    # identical) grown state
    assert [h[2] for h in r2["history"] if h[1] == 1] == \
        [h[2] for h in full["history"] if h[1] == 1]


def test_runner_mid_ligo_kill_resume_sharded(mesh_factory):
    """The sharded acceptance case: killed mid-phase on a (2, 4) mesh and
    resumed on the SAME mesh, the run matches the uninterrupted sharded run
    ≤1e-6 (same programs, carry bit-round-tripped). Resumed on a DIFFERENT
    (2, 2) mesh, the replicated carry restores elastically and the job
    completes with genuinely partitioned leaves — no parity claim there:
    cross-mesh reduction orders shift the losses, so an *adaptive* policy
    may legitimately fire at a different step."""
    mesh = mesh_factory((2, 4), ("data", "model"))
    r2 = _runner_kill_resume_mid_ligo(mesh, mesh)
    with tempfile.TemporaryDirectory() as d:
        full = TrajectoryRunner(AUTO_TRAJ, ckpt_dir=d, mesh=mesh,
                                verbose=False).run()
    assert r2["global_step"] == full["global_step"]
    assert r2["decisions"][-1]["stage_step"] == \
        full["decisions"][-1]["stage_step"]
    assert_trees_close_normalized(r2["params"], full["params"], rel=1e-6)

    mesh2 = mesh_factory((2, 2), ("data", "model"))
    r_elastic = _runner_kill_resume_mid_ligo(mesh, mesh2)
    assert r_elastic["status"] == "done"
    assert sum(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(r_elastic["params"])) > 0


# ---------------------------------------------------------------------------
# Clear errors for optimizer state that predates grow_state
# ---------------------------------------------------------------------------
def test_grow_refuses_pre_growstate_opt_state():
    params, opt = _pretrained_small(steps=2)
    with pytest.raises(ValueError, match="missing.*predates grow_state"):
        grow(params, T0, T1, method="stackbert", opt_state=sgd_init(params))
    with pytest.raises(ValueError, match="missing.*predates grow_state"):
        grow(params, T0, T1, method="stackbert",
             opt_state={"m": opt.m, "v": opt.v})
    other = adamw_init({"only": jnp.zeros((2, 2))})
    with pytest.raises(ValueError, match="does not mirror"):
        grow(params, T0, T1, method="stackbert", opt_state=other)
    # a well-formed state still rides through untouched
    big, info = grow(params, T0, T1, method="stackbert", opt_state=opt,
                     key=jax.random.PRNGKey(0))
    assert int(info["opt_state"].count) == int(opt.count)


def test_runner_clear_error_on_checkpoint_missing_opt():
    """A trajectory checkpoint without optimizer state (written before
    grow_state existed) must fail with a message naming the problem, not a
    KeyError shape crash from the restore template."""
    traj = TrajectoryConfig(stages=(Stage(T0, 3),), batch=4, seq=16,
                            checkpoint_every=2)
    params, _ = init_train_state(T0, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save_step(d, 1, {"params": params},
                  {"trajectory": traj.hash(), "stage": 0, "stage_step": 1,
                   "global_step": 1, "arch": T0.name,
                   "config": T0.config_hash()})
        with pytest.raises(ValueError, match="no optimizer state"):
            TrajectoryRunner(traj, ckpt_dir=d, verbose=False).run()


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------
def test_auto_stage_config_validation():
    with pytest.raises(ValueError, match="no policy"):
        TrajectoryConfig(stages=(Stage(T0, None),))
    with pytest.raises(ValueError, match="max_steps"):
        TrajectoryConfig(stages=(
            Stage(T0, None, policy=PolicySpec(kind="loss_plateau")),))
    with pytest.raises(ValueError, match="both"):
        TrajectoryConfig(stages=(
            Stage(T0, 5, policy=PolicySpec(kind="loss_plateau",
                                           max_steps=9)),))
    with pytest.raises(ValueError, match="unknown policy kind"):
        PolicySpec(kind="nope")
    with pytest.raises(ValueError, match="probe_candidates"):
        PolicySpec(kind="probe", max_steps=5)
    with pytest.raises(ValueError, match="unknown policy keys"):
        PolicySpec.from_json({"kind": "loss_plateau", "max_stepz": 5})


def test_from_json_auto_stage_and_hash():
    obj = {
        "arch": "llama3-8b", "smoke": True, "batch": 4, "seq": 32,
        "stages": [
            {"steps": 10, "arch": "half"},
            {"steps": "auto", "grow": "2x", "method": "ligo",
             "ligo_steps": 0, "ligo_scan_chunk": 2,
             "policy": {"kind": "rpf_decay", "max_steps": 40,
                        "min_steps": 5, "window": 6, "decay": 0.3}},
        ]}
    traj = TrajectoryConfig.from_json(obj)
    st = traj.stages[1]
    assert st.auto and st.steps is None and st.budget == 40
    assert st.policy.kind == "rpf_decay" and st.policy.decay == 0.3
    assert st.growth.ligo_scan_chunk == 2
    assert traj.has_auto_stages and traj.total_steps == 50
    assert traj.stage_bounds() == ((0, 10), (10, 50))
    # the policy block is part of the schedule identity
    obj2 = {**obj, "stages": [obj["stages"][0],
                              {**obj["stages"][1],
                               "policy": {**obj["stages"][1]["policy"],
                                          "decay": 0.5}}]}
    assert traj.hash() != TrajectoryConfig.from_json(obj2).hash()
