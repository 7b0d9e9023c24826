"""Cross-family growth: dense→MoE upcycling + the operator zoo around it.

Covers the tentpole (upcycled-MoE function preservation at init, ≤1e-6 on
logits — in practice bitwise — through the plan, the reference walk and the
sharded 8-virtual-device lane; MHA→GQA head merging vs the grouped-gamma
oracle) and the satellite fixes: the relaxed GQA lossless-cache gate (in-place migration
vs re-prefill parity on a GQA lemon hop), the config-load-time family gate in
``check_growable``, cross-family method gating in ``TrajectoryConfig``, and
the explicit paged→dense fallback in the serving engine.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, moe_target, smoke_config
from repro.configs.paper_models import BERT_SMALL
from repro.core import (apply_ligo, apply_ligo_reference, plan_for,
                        place_operator)
from repro.core import spec as S
from repro.core.grow_cache import (can_grow_cache, grow_decode_state,
                                   is_lossless_operator)
from repro.core.operators import gqa_merge_operator, lemon_operator
from repro.core.upcycle import upcycle_operator
from repro.models import init_params
from repro.optim import adamw_init, grow_adamw_state
from repro.optim.grow_state import hop_uses_grouped_gamma
from repro.serving import ServingEngine
from repro.serving.engine import make_serving_fns

# Dense source with a GQA head layout (the production shape) + its MoE twin.
DENSE = BERT_SMALL.scaled(
    name="upc-dense", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
    d_head=8, d_ff=64, vocab_size=64, max_seq=64, dtype="float32",
    norm="rms", objective="clm", encoder_only=False, causal=True,
    capacity_factor=8.0)   # drop-free MoE targets: exact preservation
MOE = moe_target(DENSE, n_experts=4, top_k=2)
MOE_PAD = moe_target(DENSE, n_experts=4, top_k=2, ff_mult=1.5)

# MHA source + GQA merge target for the head-merging operator.
MHA = DENSE.scaled(name="upc-mha", n_heads=4, n_kv_heads=4)
GQA = MHA.scaled(name="upc-gqa", n_kv_heads=2)

MESHES = [((1,), ("data",)), ((2, 4), ("data", "model"))]
MESH_IDS = ["1dev", "2x4"]


@pytest.fixture(scope="module")
def dense_params():
    return init_params(DENSE, jax.random.PRNGKey(0))


def _logits(params, cfg, toks):
    from repro.models.model import prefill
    lg, _ = prefill(params, cfg, {"tokens": toks}, max_len=toks.shape[1] + 4)
    return np.asarray(lg)


# ---------------------------------------------------------------------------
# Tentpole: upcycled MoE is the dense model's function at init
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("grow_fn", [apply_ligo, apply_ligo_reference],
                         ids=["plan", "legacy"])
@pytest.mark.parametrize("cfg2", [MOE, MOE_PAD],
                         ids=["same-ff", "padded-ff"])
def test_upcycle_function_preserving_at_init(dense_params, grow_fn, cfg2):
    """Expert replication + uniform (zero) router: `apply_moe` renormalises
    the top-k gate weights, so every token gets Σ (1/k)·MLP(x) = MLP(x) —
    logit diff ≤ 1e-6 vs the dense source (bitwise in practice), including
    with zero-padded wider experts (new columns compute exactly 0)."""
    op = upcycle_operator(DENSE, cfg2)
    big = grow_fn(op, dense_params, DENSE, cfg2)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              DENSE.vocab_size)
    lg1 = _logits(dense_params, DENSE, toks)
    lg2 = _logits(big, cfg2, toks)
    assert np.max(np.abs(lg1 - lg2)) <= 1e-6
    # structural: every expert is the dense FFN (zero-padded), router zero
    w1 = np.asarray(big["layers"]["moe"]["moe"]["w1"])
    src = np.asarray(dense_params["layers"]["attn"]["mlp"]["w1"])
    assert w1.shape[:2] == (cfg2.n_layers, cfg2.n_experts)
    for e in range(cfg2.n_experts):
        assert np.array_equal(w1[:, e, :, :src.shape[-1]], src)
    assert not np.asarray(big["layers"]["moe"]["moe"]["router"]).any()


@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
def test_upcycle_sharded_apply_matches_legacy(mesh_factory, dense_params,
                                              mesh_def):
    """The compiled GrowthPlan executor — pjit with params_pspecs-derived
    in/out shardings, expert stack landing EP/TP-sharded — produces the
    legacy walk's tree bitwise on the 8-virtual-device lane."""
    mesh = mesh_factory(*mesh_def)
    op = upcycle_operator(DENSE, MOE)
    ref = apply_ligo_reference(op, dense_params, DENSE, MOE)
    plan = plan_for(DENSE, MOE, dense_params)
    big = plan.executor(mesh=mesh)(place_operator(op, mesh), dense_params)
    ref_l = jax.tree_util.tree_leaves_with_path(ref)
    big_l = jax.tree_util.tree_leaves_with_path(big)
    assert [p for p, _ in ref_l] == [p for p, _ in big_l]
    for (_, a), (_, b) in zip(ref_l, big_l):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_upcycle_grows_adamw_moments_replicated(dense_params):
    """m and v ride the same operator (coefficient-1 expert copies square to
    themselves): every expert inherits the dense FFN's moments verbatim and
    the created router enters with zero moments — the correct state for a
    leaf whose parameter is also zero."""
    st = adamw_init(dense_params)
    # nonzero moments so replication is actually observable
    st = st._replace(
        m=jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32), st.m),
        v=jax.tree.map(lambda p: 2.0 * jnp.ones_like(p, jnp.float32), st.v))
    op = upcycle_operator(DENSE, MOE)
    st2 = grow_adamw_state(st, op, DENSE, MOE)
    for tree, val in ((st2.m, 1.0), (st2.v, 2.0)):
        w1 = np.asarray(tree["layers"]["moe"]["moe"]["w1"])
        assert w1.shape[1] == MOE.n_experts
        assert np.array_equal(w1, np.full_like(w1, val))
        assert not np.asarray(tree["layers"]["moe"]["moe"]["router"]).any()
    assert int(st2.count) == int(st.count)


def test_upcycle_through_grow_dispatch(dense_params):
    from repro.core.grow import grow
    big, info = grow(dense_params, DENSE, MOE, method="upcycle")
    assert info["method"] == "upcycle"
    assert big["layers"]["moe"]["moe"]["w2"].shape == (
        MOE.n_layers, MOE.n_experts, MOE.moe_d_ff, MOE.d_model)


# ---------------------------------------------------------------------------
# MHA→GQA head merging vs the grouped-gamma machinery
# ---------------------------------------------------------------------------
def test_gqa_merge_matches_group_mean_oracle():
    params = init_params(MHA, jax.random.PRNGKey(3))
    op = gqa_merge_operator(MHA, GQA)
    big_p = apply_ligo(op, params, MHA, GQA)
    big_l = apply_ligo_reference(op, params, MHA, GQA)
    for a, b in zip(jax.tree.leaves(big_p), jax.tree.leaves(big_l)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    dh, G = MHA.d_head, MHA.n_heads // GQA.n_kv_heads
    for leaf in ("wk", "wv"):
        src = np.asarray(params["layers"]["attn"][leaf])
        dst = np.asarray(big_p["layers"]["attn"][leaf])
        for g in range(GQA.n_kv_heads):
            grp = src[..., g * G * dh:(g + 1) * G * dh]
            mean = grp.reshape(grp.shape[:-1] + (G, dh)).mean(-2)
            np.testing.assert_allclose(dst[..., g * dh:(g + 1) * dh], mean,
                                       atol=1e-6)
    # wo rides Γ(B_v): with G1 = 1 the lift is a pure block-repeat of the
    # merge matrix over each group's query heads — no extra 1/G scaling.
    wo_src = np.asarray(params["layers"]["attn"]["wo"])
    wo_dst = np.asarray(big_p["layers"]["attn"]["wo"])
    E_kv = np.kron(np.repeat(np.eye(GQA.n_kv_heads), G, axis=1) / G,
                   np.eye(dh))
    # Γ(B_v) with G1 = 1: block-repeat each merged kv row over its G query
    # heads — no extra 1/G scaling on the output projection.
    E_direct = np.repeat(E_kv.reshape(GQA.n_kv_heads, dh, -1), G, axis=0
                         ).reshape(MHA.n_heads * dh, -1)
    np.testing.assert_allclose(wo_dst, np.einsum("oi,lij->loj", E_direct,
                                                 wo_src), atol=1e-6)


def test_gqa_merge_v_moment_uses_squared_gamma():
    """The hop engages the grouped gamma (Σcᵢ² second-moment semantics):
    v maps through the elementwise-squared expanders, which for the 1/G
    group mean gives Σ(1/G)² = 1/G² per source head — NOT the (Σ1/G)² = 1
    a linear-then-square map would give."""
    assert hop_uses_grouped_gamma(MHA, GQA)
    params = init_params(MHA, jax.random.PRNGKey(4))
    st = adamw_init(params)._replace(
        v=jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32),
                       adamw_init(params).v))
    op = gqa_merge_operator(MHA, GQA)
    st2 = grow_adamw_state(st, op, MHA, GQA)
    G = MHA.n_heads // GQA.n_kv_heads
    v_wk = np.asarray(st2.v["layers"]["attn"]["wk"])
    # each merged kv column sums G squared coefficients (1/G)² over unit v
    np.testing.assert_allclose(v_wk, np.full_like(v_wk, G * (1 / G) ** 2),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Satellite 3: check_growable family gate (config-load-time, named pair)
# ---------------------------------------------------------------------------
def test_check_growable_names_unsupported_family_pair():
    ssm = smoke_config(get_config("xlstm-125m"))
    with pytest.raises(ValueError, match="family hop"):
        S.check_growable(DENSE, ssm)
    with pytest.raises(ValueError, match=ssm.name):
        S.check_growable(ssm, DENSE)


def test_check_growable_allows_and_validates_upcycle_pair():
    S.check_growable(DENSE, MOE)                     # the supported hop
    with pytest.raises(ValueError, match="d_ff == 0"):
        S.check_growable(DENSE.scaled(name="noff", d_ff=0), MOE)
    with pytest.raises(ValueError, match="rms-norm"):
        S.check_growable(DENSE.scaled(name="ln", norm="layer"),
                         MOE.scaled(name="ln-moe", norm="layer"))


def test_check_growable_width_space_mismatch_is_valueerror():
    """A d_ff=0 source growing into d_ff>0 used to die much later as a bare
    KeyError inside expander resolution; now it's a load-time ValueError."""
    no_ff = DENSE.scaled(name="noff2", d_ff=0)
    with pytest.raises(ValueError, match="width expander spaces"):
        S.check_growable(no_ff, DENSE)


def test_trajectory_config_gates_cross_family_methods():
    from repro.trajectory.config import TrajectoryConfig
    base = {"arch": "llama3-8b", "smoke": True,
            "stages": [{"steps": 2},
                       {"steps": 2, "grow": "moe", "method": "stackbert"}]}
    with pytest.raises(ValueError, match="family hop|cannot cross"):
        TrajectoryConfig.from_json(base)
    base["stages"][1]["method"] = "upcycle"
    tc = TrajectoryConfig.from_json(base)            # upcycle crosses fine
    assert tc.stages[1].cfg.family == "moe"
    assert tc.stages[1].cfg.n_experts > 0


# ---------------------------------------------------------------------------
# Satellite 1: relaxed lossless-cache gate + GQA migration parity
# ---------------------------------------------------------------------------
def test_lossless_gate_accepts_layout_preserving_gqa_and_upcycle():
    gqa_wide = DENSE.scaled(name="upc-gqa-ff2", d_ff=DENSE.d_ff * 2)
    op = lemon_operator(DENSE, gqa_wide)             # GQA on both sides
    assert is_lossless_operator(op, DENSE, gqa_wide)
    assert can_grow_cache(DENSE, gqa_wide)
    # the dense→MoE upcycle is lossless and cache-growable across families
    up = upcycle_operator(DENSE, MOE)
    assert is_lossless_operator(up, DENSE, MOE)
    assert can_grow_cache(DENSE, MOE)
    # changed GQA head layout still refuses (wo's grouped fan-in averages)
    more_heads = DENSE.scaled(name="upc-gqa-h8", n_heads=8)
    assert not is_lossless_operator(
        {"width": {}, "depth": {}}, DENSE, more_heads)


def _mid_flight_engine(params, cfg, *, mesh=None):
    eng = ServingEngine(params, cfg, slots=2, prompt_budget=8, gen_budget=12,
                        mesh=mesh)
    rng = np.random.RandomState(0)
    for i in range(4):
        eng.submit(list(rng.randint(0, cfg.vocab_size, 4 + i % 4)),
                   max_new=12)
    for _ in range(3):
        eng.step()
    assert eng.live
    return eng


@pytest.mark.parametrize("hop", ["gqa-lemon", "upcycle"])
@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
def test_inplace_migration_matches_reprefill(mesh_factory, dense_params,
                                             hop, mesh_def):
    """In-place cache growth (now allowed on GQA layout-preserving hops and
    on the dense→MoE upcycle) vs the universal re-prefill oracle: served
    logits agree ≤1e-5 for both, and bitwise vs the small model's own
    continued decode on a single device (the hops are lossless)."""
    mesh = mesh_factory(*mesh_def)
    if hop == "gqa-lemon":
        cfg2 = DENSE.scaled(name="upc-gqa-ff2", d_ff=DENSE.d_ff * 2)
        op = lemon_operator(DENSE, cfg2)
    else:
        cfg2 = MOE
        op = upcycle_operator(DENSE, cfg2)
    big = apply_ligo(op, dense_params, DENSE, cfg2)

    eng = _mid_flight_engine(dense_params, DENSE, mesh=mesh)
    migrated = grow_decode_state(eng.state, op, DENSE, cfg2, mesh=mesh)
    oracle = eng.reprefill_state(big, cfg2)

    _, decode, _ = make_serving_fns(cfg2, eng.max_len)
    _, decode_small, _ = make_serving_fns(DENSE, eng.max_len)
    live = [i for i, r in enumerate(eng.slot_req) if r is not None]
    last = np.zeros((eng.slots, 1), np.int32)
    for i in live:
        last[i, 0] = eng.slot_req[i].tokens[-1]
    toks = jnp.asarray(last)
    sa, sb, ss = migrated, oracle, eng.state
    for _ in range(4):
        la, sa, pa = decode(big, sa, toks)
        lb, sb, _ = decode(big, sb, toks)
        ls, ss, _ = decode_small(dense_params, ss, toks)
        la, lb, ls = (np.asarray(x) for x in (la, lb, ls))
        # the program's greedy pick is the host's argmax of its logits
        np.testing.assert_array_equal(np.asarray(pa), np.argmax(la, -1))
        if math.prod(mesh_def[0]) == 1:
            assert np.array_equal(la[live], ls[live])
        else:
            np.testing.assert_allclose(la[live], ls[live], rtol=2e-6,
                                       atol=2e-7)
        np.testing.assert_allclose(la[live], lb[live], rtol=1e-5, atol=1e-5)
        toks = jnp.asarray(np.argmax(la, -1)[:, None])


# ---------------------------------------------------------------------------
# Satellite 2: paged→dense fallback is loud
# ---------------------------------------------------------------------------
def test_paged_fallback_warns_and_reports():
    windowed = DENSE.scaled(name="upc-win", window=16)
    params = init_params(windowed, jax.random.PRNGKey(5))
    with pytest.warns(UserWarning, match="paged KV layout unsupported"):
        eng = ServingEngine(params, windowed, slots=2, prompt_budget=8,
                            gen_budget=8, kv_layout="paged")
    assert eng.kv_layout == "dense"
    assert eng.kv_layout_requested == "paged"
    assert eng.kv_fallback
    # a supported config keeps the requested layout, no fallback flag
    eng2 = ServingEngine(init_params(DENSE, jax.random.PRNGKey(5)), DENSE,
                         slots=2, prompt_budget=8, gen_budget=8,
                         kv_layout="paged")
    assert eng2.kv_layout == "paged" and not eng2.kv_fallback
