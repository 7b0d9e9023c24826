"""Growth trajectories: optimizer-state growth semantics (first moment
linear, second moment through squared expanders, count preserved, decay mask
rebuilt), and the multi-stage runner — train→grow→train as one resumable
job whose checkpoints land on the correct (stage, step) after a mid-stage
kill, unsharded and under a mesh (the forced-8-device CI lane runs the
sharded cases for real)."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_trees_close_normalized

from repro.checkpoint import CheckpointManager
from repro.configs.base import TrainConfig
from repro.configs.paper_models import BERT_SMALL
from repro.core import (apply_ligo, apply_ligo_reference, compose_chain,
                        grow, init_ligo_params)
from repro.core import operators as cops
from repro.data import batch_for_step
from repro.optim import (adamw_init, grow_adamw_state,
                         grow_adamw_state_chain, hop_uses_grouped_gamma)
from repro.trajectory import (GrowthSpec, Stage, TrajectoryConfig,
                              TrajectoryRunner)
from repro.training import init_train_state, make_train_step

T0 = BERT_SMALL.scaled(name="tr0", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=4, d_head=8, d_ff=64, vocab_size=64,
                       max_seq=64, dtype="float32", objective="clm",
                       encoder_only=False, causal=True)
T1 = T0.scaled(name="tr1", n_layers=3, d_model=48, n_heads=6, n_kv_heads=6,
               d_ff=96)
T2 = T1.scaled(name="tr2", n_layers=4, d_model=64, n_heads=8, n_kv_heads=8,
               d_ff=128)

TRAJ = TrajectoryConfig(stages=(
    Stage(T0, 5),
    Stage(T1, 5, GrowthSpec(method="ligo", ligo_steps=2)),
    Stage(T2, 5, GrowthSpec(method="stackbert"))),
    batch=4, seq=16, lr=1e-3, checkpoint_every=3)


def _pretrained_small(steps=8):
    params, opt = init_train_state(T0, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        T0, TrainConfig(steps=steps, warmup_steps=2, lr=1e-3)))
    for i in range(steps):
        b = {k: jnp.asarray(v)
             for k, v in batch_for_step(T0, i, 4, 16, seed=0).items()}
        params, opt, _ = step(params, opt, b, jnp.asarray(i))
    return params, opt


# ---------------------------------------------------------------------------
# Optimizer-state growth
# ---------------------------------------------------------------------------
def test_grow_adamw_state_matches_oracle():
    """m maps through the operator, v through the resolve-then-squared
    operator (reference-walk oracles), count is preserved and v stays ≥ 0."""
    params, opt = _pretrained_small()
    op = init_ligo_params(jax.random.PRNGKey(3), T0, T1)
    grown = grow_adamw_state(opt, op, T0, T1)

    m_ref = apply_ligo_reference(op, opt.m, T0, T1)
    v_ref = apply_ligo_reference(op, opt.v, T0, T1, square=True)
    assert_trees_close_normalized(grown.m, m_ref, rel=1e-5)
    assert_trees_close_normalized(grown.v, v_ref, rel=1e-5)
    assert int(grown.count) == int(opt.count) == 8
    for leaf in jax.tree.leaves(grown.v):
        assert float(jnp.min(leaf)) >= 0.0, "squared-operator v went negative"
    # structure mirrors the grown parameter tree exactly
    big = apply_ligo(op, params, T0, T1)
    assert (jax.tree.map(lambda a: a.shape, grown.m)
            == jax.tree.map(lambda a: a.shape, big))


def test_grow_zero_state_parity_with_fresh_baseline():
    """Growing an all-zero AdamW state is exactly a fresh init (linear map
    of zeros), so the first post-growth train step from grown-zero moments
    equals the fresh-moments baseline bit-for-bit — the zero-information
    parity point of the moment-carrying semantics."""
    params, _ = _pretrained_small()
    op = init_ligo_params(jax.random.PRNGKey(3), T0, T1)
    big = apply_ligo(op, params, T0, T1)

    grown = grow_adamw_state(adamw_init(params), op, T0, T1)
    fresh = adamw_init(big)
    for a, b in zip(jax.tree.leaves(grown), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    step = jax.jit(make_train_step(
        T1, TrainConfig(steps=10, warmup_steps=2, lr=1e-3)))
    b0 = {k: jnp.asarray(v)
          for k, v in batch_for_step(T1, 0, 4, 16, seed=1).items()}
    p_a, s_a, m_a = step(big, grown, b0, jnp.asarray(1))
    p_b, s_b, m_b = step(big, fresh, b0, jnp.asarray(1))
    np.testing.assert_array_equal(np.asarray(m_a["total"]),
                                  np.asarray(m_b["total"]))
    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grown_moments_step_differs_from_fresh_and_trains():
    """With real (nonzero) small-model moments the grown state changes the
    first post-growth update (no silent fallback to re-warming), the decay
    mask is rebuilt for the new tree shape, and the schedule count
    continues."""
    params, opt = _pretrained_small()
    assert int(opt.count) > 0
    op = init_ligo_params(jax.random.PRNGKey(3), T0, T1)
    big = apply_ligo(op, params, T0, T1)
    grown = grow_adamw_state(opt, op, T0, T1)

    step = jax.jit(make_train_step(
        T1, TrainConfig(steps=10, warmup_steps=2, lr=1e-3)))
    b0 = {k: jnp.asarray(v)
          for k, v in batch_for_step(T1, 0, 4, 16, seed=1).items()}
    # step index 1: inside warmup but with a non-zero lr, so the moment
    # carry actually shows up in the update
    p_g, s_g, m_g = step(big, grown, b0, jnp.asarray(1))
    p_f, _, _ = step(big, adamw_init(big), b0, jnp.asarray(1))
    assert np.isfinite(float(m_g["total"]))
    assert int(s_g.count) == int(opt.count) + 1
    diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(jax.tree.leaves(p_g), jax.tree.leaves(p_f))]
    assert max(diffs) > 0.0, "grown moments had no effect on the update"


def test_grow_via_grow_api_carries_opt_state():
    """grow(..., opt_state=...) returns the grown state in info for every
    operator method; method='random' resets to adamw_init."""
    params, opt = _pretrained_small()
    big, info = grow(params, T0, T1, method="stackbert",
                     key=jax.random.PRNGKey(1), opt_state=opt)
    assert int(info["opt_state"].count) == int(opt.count)
    assert any(float(jnp.abs(x).max()) > 0
               for x in jax.tree.leaves(info["opt_state"].m))
    big_r, info_r = grow(params, T0, T1, method="random",
                         key=jax.random.PRNGKey(1), opt_state=opt)
    assert int(info_r["opt_state"].count) == 0
    assert all(float(jnp.abs(x).max()) == 0
               for x in jax.tree.leaves(info_r["opt_state"].m))


@pytest.mark.parametrize("mesh_def", [((1,), ("data",)),
                                      ((2, 4), ("data", "model"))],
                         ids=["1dev", "2x4"])
def test_grow_adamw_state_sharded_parity(mesh_factory, mesh_def):
    """Sharded optimizer-state growth (moments ride the mesh executor like
    the weights) matches the unsharded result ≤1e-6 on both device lanes."""
    mesh = mesh_factory(*mesh_def)
    _, opt = _pretrained_small()
    op = init_ligo_params(jax.random.PRNGKey(3), T0, T1)
    want = grow_adamw_state(opt, op, T0, T1)
    got = grow_adamw_state(opt, op, T0, T1, mesh=mesh)
    assert_trees_close_normalized(got, want, rel=1e-6)


# ---------------------------------------------------------------------------
# Trajectory runner: kill mid-stage → resume at the correct (stage, step)
# ---------------------------------------------------------------------------
def _check_kill_resume(mesh, tmpdir, resume_mesh=None):
    r1 = TrajectoryRunner(TRAJ, ckpt_dir=tmpdir, mesh=mesh,
                          verbose=False).run(max_steps=8)
    assert r1["status"] == "paused"
    assert (r1["stage"], r1["stage_step"]) == (1, 3)

    # the checkpoint on disk records the mid-trajectory position
    meta = CheckpointManager(tmpdir).latest_meta()
    assert meta["trajectory"] == TRAJ.hash()
    assert (meta["stage"], meta["stage_step"]) == (1, 3)
    assert meta["arch"] == T1.name

    r2 = TrajectoryRunner(TRAJ, ckpt_dir=tmpdir,
                          mesh=resume_mesh if resume_mesh is not None
                          else mesh,
                          verbose=False).run()
    assert r2["resumed_at"] == (1, 3)
    assert r2["status"] == "done"
    assert r2["cfg"].name == T2.name
    assert r2["global_step"] == TRAJ.total_steps
    assert all(np.isfinite(l) for _, _, l in r2["history"])
    return r2


def test_trajectory_kill_and_resume_deterministic():
    """A 3-stage trajectory killed mid-stage resumes from restore_latest at
    the correct stage/step and reproduces the uninterrupted run exactly."""
    with tempfile.TemporaryDirectory() as d:
        r2 = _check_kill_resume(None, d)
    with tempfile.TemporaryDirectory() as d:
        full = TrajectoryRunner(TRAJ, ckpt_dir=d, mesh=None,
                                verbose=False).run()
    np.testing.assert_allclose(full["history"][-1][2], r2["history"][-1][2],
                               rtol=1e-5)
    assert_trees_close_normalized(r2["params"], full["params"], rel=1e-5)


def test_trajectory_sharded_end_to_end(mesh_factory):
    """The acceptance case: the 3-stage trajectory runs end-to-end sharded
    on a (2, 4) (data, model) mesh — growth through the sharded GrowthPlan,
    train steps pjit'd — is killed mid-stage and resumes at the correct
    stage *on a different mesh* (elastic: restore shardings rebuilt from
    the resuming mesh); final leaves land genuinely partitioned."""
    mesh = mesh_factory((2, 4), ("data", "model"))
    mesh2 = mesh_factory((2, 2), ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        r2 = _check_kill_resume(mesh, d, resume_mesh=mesh2)
    partitioned = sum(not leaf.sharding.is_fully_replicated
                      for leaf in jax.tree.leaves(r2["params"]))
    assert partitioned > 0, "no parameter leaf partitioned on an 8-way mesh"
    partitioned_m = sum(not leaf.sharding.is_fully_replicated
                        for leaf in jax.tree.leaves(r2["opt"].m))
    assert partitioned_m > 0, "grown optimizer moments not partitioned"


def test_trajectory_refuses_foreign_checkpoint():
    """A checkpoint directory written by a different schedule must be
    rejected at resume (trajectory hash mismatch), not silently reused."""
    other = TrajectoryConfig(stages=(Stage(T0, 3),), batch=4, seq=16,
                             checkpoint_every=2)
    with tempfile.TemporaryDirectory() as d:
        TrajectoryRunner(other, ckpt_dir=d, verbose=False).run()
        with pytest.raises(ValueError, match="trajectory"):
            TrajectoryRunner(TRAJ, ckpt_dir=d, verbose=False).run()


def test_trajectory_config_validation_and_hash():
    with pytest.raises(ValueError):
        TrajectoryConfig(stages=())
    with pytest.raises(ValueError):            # stage 0 must not grow
        TrajectoryConfig(stages=(Stage(T0, 3, GrowthSpec()),))
    with pytest.raises(ValueError):            # later stages must grow
        TrajectoryConfig(stages=(Stage(T0, 3), Stage(T1, 3)))
    with pytest.raises(ValueError):            # non-growable pair
        TrajectoryConfig(stages=(Stage(T1, 3),
                                 Stage(T0, 3, GrowthSpec())))
    a = TRAJ.hash()
    b = TrajectoryConfig(stages=TRAJ.stages, batch=TRAJ.batch, seq=TRAJ.seq,
                         lr=TRAJ.lr,
                         checkpoint_every=TRAJ.checkpoint_every).hash()
    assert a == b                              # hash is pure data
    c = TrajectoryConfig(stages=TRAJ.stages, batch=8, seq=TRAJ.seq).hash()
    assert a != c


def test_trajectory_from_json_resolution():
    """JSON stage resolution: 'half' source, '2x' hops relative to the
    previous stage, explicit growth budgets."""
    traj = TrajectoryConfig.from_json({
        "arch": "llama3-8b", "smoke": True, "batch": 4, "seq": 32,
        "checkpoint_every": 5,
        "stages": [
            {"steps": 10, "arch": "half"},
            {"steps": 10, "grow": "2x", "method": "ligo", "ligo_steps": 4},
            {"steps": 10, "grow": "2x", "method": "bert2bert"},
        ]})
    names = [st.cfg.name for st in traj.stages]
    assert names[0].endswith("-half")
    assert names[1].endswith("-half-grown")
    assert names[2].endswith("-half-grown-grown")
    assert traj.stages[1].growth.ligo_steps == 4
    assert traj.stages[2].growth.method == "bert2bert"
    assert traj.total_steps == 30
    assert traj.stage_bounds() == ((0, 10), (10, 20), (20, 30))


# ---------------------------------------------------------------------------
# GQA second-moments rule: v per hop under grouped gamma (skip-stage path)
# ---------------------------------------------------------------------------
# GQA chain (kv < heads at every hop, constant d_head so one-hot selection
# operators apply): gamma group-averages here, so squared operators do NOT
# compose — the very divergence the chain rule exists for.
G0 = BERT_SMALL.scaled(name="gq0", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, d_head=8, d_ff=64, vocab_size=64,
                       max_seq=64, dtype="float32", objective="clm",
                       encoder_only=False, causal=True)
G1 = G0.scaled(name="gq1", n_layers=3, d_model=48, n_heads=6, n_kv_heads=2,
               d_ff=96)
G2 = G1.scaled(name="gq2", n_layers=4, d_model=64, n_heads=8, n_kv_heads=4,
               d_ff=128)


def _pretrained(cfg, steps=6, seed=0):
    params, opt = init_train_state(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(
        cfg, TrainConfig(steps=steps, warmup_steps=2, lr=1e-3)))
    for i in range(steps):
        b = {k: jnp.asarray(v)
             for k, v in batch_for_step(cfg, i, 4, 16, seed=seed).items()}
        params, opt, _ = step(params, opt, b, jnp.asarray(i))
    return params, opt


def test_gqa_squared_operators_do_not_compose():
    """Σcᵢ² vs (Σcᵢ)²: under grouped heads, even ONE-HOT selection factors
    (which square-compose exactly on MHA — test_compose) diverge between
    squaring per hop and squaring the composed operator, because gamma
    column-averages each kv group (/G) before the square is taken."""
    assert hop_uses_grouped_gamma(G0, G1)
    assert not hop_uses_grouped_gamma(T0, T1)
    _, opt = _pretrained(G0)
    op_a = cops.stackbert_operator(G0, G1, key=jax.random.PRNGKey(1))
    op_b = cops.stackbert_operator(G1, G2, key=jax.random.PRNGKey(2))
    mid = apply_ligo_reference(op_a, opt.v, G0, G1, square=True)
    v_seq = apply_ligo_reference(op_b, mid, G1, G2, square=True)
    from repro.core import compose_ligo
    composed = compose_ligo(op_a, op_b, G0, G1, G2)
    v_comp = apply_ligo_reference(composed, opt.v, G0, G2, square=True)
    rel = max(float(np.abs(np.asarray(a) - np.asarray(b)).max()
                    / (np.abs(np.asarray(b)).max() + 1e-30))
              for a, b in zip(jax.tree.leaves(v_comp),
                              jax.tree.leaves(v_seq)))
    assert rel > 1e-3, f"expected Σc² vs (Σc)² divergence, got rel={rel}"


def test_grow_adamw_state_chain_gqa_rule():
    """The chain rule: m through the composed operator, v per hop when any
    hop's gamma group-averages — so a skip-stage restart produces the same
    moments a stage-by-stage run would (LEMON-exact)."""
    _, opt = _pretrained(G0)
    chain = [G0, G1, G2]
    ops_list = [init_ligo_params(jax.random.PRNGKey(1), G0, G1),
                init_ligo_params(jax.random.PRNGKey(2), G1, G2)]
    grown = grow_adamw_state_chain(opt, ops_list, chain)

    # v: hop-by-hop squared oracle (what the stage-by-stage run does)
    v_ref = opt.v
    m_ref = opt.m
    for op, a, b in zip(ops_list, chain[:-1], chain[1:]):
        v_ref = apply_ligo_reference(op, v_ref, a, b, square=True)
        m_ref = apply_ligo_reference(op, m_ref, a, b)
    assert_trees_close_normalized(grown.v, v_ref, rel=1e-5)
    # m: linear, so composed == sequential — both are the right answer
    assert_trees_close_normalized(grown.m, m_ref, rel=1e-5)
    assert int(grown.count) == int(opt.count)
    for leaf in jax.tree.leaves(grown.v):
        assert float(jnp.min(leaf)) >= 0.0

    # MHA chain keeps the composed fast path for v too
    m0, m1, m2 = (c.scaled(name=c.name + "m", n_kv_heads=c.n_heads)
                  for c in chain)
    _, opt_m = _pretrained(m0)
    mops = [cops.stackbert_operator(m0, m1, key=jax.random.PRNGKey(1)),
            cops.stackbert_operator(m1, m2, key=jax.random.PRNGKey(2))]
    grown_m = grow_adamw_state_chain(opt_m, mops, [m0, m1, m2])
    comp = compose_chain(mops, [m0, m1, m2])
    v_comp = apply_ligo_reference(comp, opt_m.v, m0, m2, square=True)
    assert_trees_close_normalized(grown_m.v, v_comp, rel=1e-5)


def test_runner_collapses_zero_step_stages_lemon_exact():
    """Consecutive zero-step stages run as ONE composed fused hop (the
    skip-stage path): the runner's stage-entry snapshot must equal the
    analytic oracle — params and m through the composed operator, v per hop
    (GQA rule) — and no intermediate-stage checkpoint may exist."""
    traj = TrajectoryConfig(stages=(
        Stage(G0, 2),
        Stage(G1, 0, GrowthSpec(method="ligo", ligo_steps=0)),
        Stage(G2, 2, GrowthSpec(method="ligo", ligo_steps=0))),
        batch=4, seq=16, lr=1e-3, checkpoint_every=3)
    with tempfile.TemporaryDirectory() as d:
        r = TrajectoryRunner(traj, ckpt_dir=d, verbose=False).run()
        assert r["status"] == "done"
        # stage 1 was skipped through: no train/grow timing, no checkpoint
        assert 1 not in r["timings"]
        from repro.checkpoint.io import list_steps, load_meta
        assert all(load_meta(d, s)["stage"] != 1 for s in list_steps(d))
        # the stage-2 entry snapshot (post-growth, global step 2)
        mgr = CheckpointManager(d)
        tmpl = {"params": jax.eval_shape(
                    lambda: init_train_state(G2, jax.random.PRNGKey(0))[0]),
                "opt": jax.eval_shape(
                    adamw_init, jax.eval_shape(
                        lambda: init_train_state(
                            G2, jax.random.PRNGKey(0))[0]))}
        snap, meta = mgr.restore(2, tmpl)
        assert meta["stage"] == 2 and meta["stage_step"] == 0

    # oracle: replicate stage 0 exactly, then the composed hop by hand
    p0, opt0 = init_train_state(G0, jax.random.PRNGKey(traj.seed))
    tcfg = TrainConfig(steps=2, warmup_steps=1, lr=traj.lr,
                       seq_len=traj.seq, global_batch=traj.batch)
    step = jax.jit(make_train_step(G0, tcfg))
    for i in range(2):
        b = {k: jnp.asarray(v) for k, v in
             batch_for_step(G0, i, traj.batch, traj.seq,
                            seed=traj.seed).items()}
        p0, opt0, _ = step(p0, opt0, b, jnp.asarray(i))
    ops_list = [init_ligo_params(jax.random.PRNGKey(traj.seed + 7 * 1),
                                 G0, G1),
                init_ligo_params(jax.random.PRNGKey(traj.seed + 7 * 2),
                                 G1, G2)]
    comp = compose_chain(ops_list, [G0, G1, G2])
    want_p = apply_ligo(comp, p0, G0, G2)
    want_m = apply_ligo(comp, opt0.m, G0, G2)
    want_v = opt0.v
    for op, a, b in zip(ops_list, [G0, G1], [G1, G2]):
        want_v = apply_ligo_reference(op, want_v, a, b, square=True)
    assert_trees_close_normalized(snap["params"], want_p, rel=1e-5)
    assert_trees_close_normalized(snap["opt"].m, want_m, rel=1e-5)
    assert_trees_close_normalized(snap["opt"].v, want_v, rel=1e-5)


def test_supervisor_threads_meta_into_checkpoints():
    """Supervisor.run(meta=...) stamps the run identity on every checkpoint
    it writes — the dict launch/train.py consumes (and validates) on
    elastic resume."""
    params, opt = init_train_state(T0, jax.random.PRNGKey(0))
    from repro.distributed.supervisor import Supervisor
    step = jax.jit(make_train_step(
        T0, TrainConfig(steps=4, warmup_steps=2, lr=1e-3)))
    batch_at = lambda s: {k: jnp.asarray(v) for k, v in  # noqa: E731
                          batch_for_step(T0, s, 4, 16, seed=0).items()}
    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(ckpt_dir=d, checkpoint_every=2)
        # the injected fault forces a restore mid-run: the restored meta
        # must NOT leak into later saves (a stale "step" there would corrupt
        # both replay and any later resume)
        sup.run({"params": params, "opt": opt},
                lambda p, o, b, s: step(p, o, b, jnp.asarray(s)),
                batch_at, start_step=0, steps=4,
                fail_at={3: RuntimeError("boom")},
                meta={"arch": T0.name, "config": T0.config_hash()})
        from repro.checkpoint.io import list_steps, load_meta
        for s in list_steps(d):
            meta = load_meta(d, s)
            assert meta["step"] == s, (s, meta)
            assert meta["arch"] == T0.name
            assert meta["config"] == T0.config_hash()
        assert sup.mgr.latest_meta()["step"] == 4
