"""GrowthPlan engine: plan/fused output == apply_ligo_reference for every
grow method, custom_vjp gradients == einsum-reference gradients (fused Pallas
fwd+bwd kernels in interpret mode), one kernel launch per leaf group,
universal eligibility (4-D MoE stacks, non-128-aligned dims), single-trace
LiGO phase, and once-per-apply expander resolution."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, grow_target, smoke_config
from repro.configs.paper_models import BERT_SMALL
from repro.core import (TRACE_COUNTS, apply_ligo, apply_ligo_reference,
                        init_ligo_params, plan_for, train_ligo)
from repro.core import operators as ops
from repro.core.plan import RESOLVE_COUNTS
from repro.kernels import (LAUNCH_COUNTS, ligo_blend_expand_ref,
                           ligo_blend_expand_vjp)
from repro.models import init_params

CFG1 = BERT_SMALL.scaled(name="gp1", n_layers=2, d_model=32, n_heads=4,
                         n_kv_heads=4, d_head=8, d_ff=64, vocab_size=64,
                         max_seq=64, dtype="float32")
# deeper + wider, equal d_head so the selection-copy baselines apply too
CFG2 = CFG1.scaled(name="gp2", n_layers=4, d_model=64, n_heads=8,
                   n_kv_heads=8, d_head=8, d_ff=128)


@pytest.fixture(scope="module")
def small_params():
    return init_params(CFG1, jax.random.PRNGKey(0))


def _operator(method: str):
    key = jax.random.PRNGKey(7)
    if method == "ligo":
        return init_ligo_params(key, CFG1, CFG2)
    if method == "stackbert":
        return ops.stackbert_operator(CFG1, CFG2, key=key)
    if method == "interpolation":
        return ops.interpolation_operator(CFG1, CFG2, key=key)
    if method == "net2net":
        return ops.net2net_operator(key, CFG1, CFG2)
    if method == "bert2bert":
        return ops.bert2bert_operator(key, CFG1, CFG2)
    raise ValueError(method)


METHODS = ("ligo", "stackbert", "interpolation", "net2net", "bert2bert")


@pytest.mark.parametrize("method", METHODS)
def test_plan_matches_legacy(small_params, method):
    op = _operator(method)
    legacy = apply_ligo_reference(op, small_params, CFG1, CFG2)
    plan = apply_ligo(op, small_params, CFG1, CFG2)
    assert jax.tree.structure(legacy) == jax.tree.structure(plan)
    for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(plan)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_kernel_path_matches_legacy(small_params):
    """use_kernel=True routes eligible groups through the Pallas custom_vjp
    (interpret mode on CPU) — output must still match the legacy walk."""
    op = _operator("ligo")
    legacy = apply_ligo_reference(op, small_params, CFG1, CFG2)
    plan = plan_for(CFG1, CFG2, small_params)
    assert any(g.kernel_ok for g in plan.groups), \
        "no fused-eligible groups on the attn family"
    fused = plan.apply(op, small_params, use_kernel=True)
    for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


from conftest import assert_trees_close_normalized


def _loss(lg, apply):
    big = apply(lg)
    return sum(jnp.sum(x * x) for x in jax.tree.leaves(big))


def _assert_grads_close(g_ref, g_got, rel=1e-5):
    assert_trees_close_normalized(g_got, g_ref, rel=rel)


def test_plan_gradients_match_legacy(small_params):
    op = _operator("ligo")
    plan = plan_for(CFG1, CFG2, small_params)

    g_legacy = jax.grad(lambda l: _loss(l, lambda l: apply_ligo_reference(
        l, small_params, CFG1, CFG2)))(op)
    for use_kernel in (False, True):
        g_plan = jax.grad(lambda l: _loss(l, lambda l: plan.apply(
            l, small_params, use_kernel=use_kernel)))(op)
        for a, b in zip(jax.tree.leaves(g_legacy), jax.tree.leaves(g_plan)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("method", METHODS)
def test_fused_bwd_matches_legacy_grad_all_methods(small_params, method):
    """jax.grad through the fused Pallas fwd+bwd kernels (interpret mode)
    == jax.grad of apply_ligo_reference to ≤ 1e-5 relative error, for every
    growth method's operator tree."""
    op = _operator(method)
    plan = plan_for(CFG1, CFG2, small_params)
    g_legacy = jax.grad(lambda l: _loss(l, lambda l: apply_ligo_reference(
        l, small_params, CFG1, CFG2)))(op)
    g_fused = jax.grad(lambda l: _loss(l, lambda l: plan.apply(
        l, small_params, use_kernel=True)))(op)
    _assert_grads_close(g_legacy, g_fused, rel=1e-5)


# --- universal eligibility: 4-D MoE expert stacks ---------------------------
MOE1 = smoke_config(get_config("mixtral-8x7b"))
MOE2 = grow_target(MOE1)


def test_one_kernel_launch_per_group():
    """The fused path folds each leaf group (and any MoE expert dim) into a
    single kernel grid: tracing one apply issues exactly one forward launch
    per eligible group, and one fused multi-cotangent backward launch per
    eligible group under grad — never one per leaf (the MoE pair batches
    moe/w1 + moe/w3 × E experts into one group, so per-leaf unrolling would
    show up as extra launches here)."""
    sp = init_params(MOE1, jax.random.PRNGKey(0))
    lg = init_ligo_params(jax.random.PRNGKey(1), MOE1, MOE2)
    plan = plan_for(MOE1, MOE2, sp)
    eligible = [g for g in plan.groups if g.kernel_ok]
    n_leaves = sum(len(g.paths) for g in eligible)
    assert eligible and n_leaves > len(eligible), \
        "need a multi-leaf eligible group for this test to bite"

    LAUNCH_COUNTS.clear()
    jax.eval_shape(lambda l: plan.apply(l, sp, use_kernel=True), lg)
    assert LAUNCH_COUNTS["fwd"] == len(eligible), \
        (dict(LAUNCH_COUNTS), len(eligible), n_leaves)
    # every other group is counted on the einsum route, never silently
    assert LAUNCH_COUNTS["einsum"] == len(plan.groups) - len(eligible)
    assert plan.kernel_groups() == (len(eligible), len(plan.groups))

    LAUNCH_COUNTS.clear()
    jax.eval_shape(jax.grad(lambda l: _loss(l, lambda l: plan.apply(
        l, sp, use_kernel=True))), lg)
    assert LAUNCH_COUNTS["fwd"] == len(eligible)
    assert LAUNCH_COUNTS["bwd"] == len(eligible), dict(LAUNCH_COUNTS)

# --- universal eligibility: non-128-aligned widths (rejected pre-PR) --------
NA1 = BERT_SMALL.scaled(name="na1", n_layers=2, d_model=36, n_heads=4,
                        n_kv_heads=4, d_head=9, d_ff=60, vocab_size=64,
                        max_seq=64, dtype="float32")
NA2 = NA1.scaled(name="na2", n_layers=4, d_model=100, n_heads=10,
                 n_kv_heads=10, d_head=10, d_ff=180)


@pytest.mark.parametrize("pair", [(MOE1, MOE2), (NA1, NA2)],
                         ids=["moe-4d", "non-aligned"])
def test_fused_path_universal_coverage(pair):
    """MoE (L1, E, a, b) expert stacks and non-128-aligned widths run the
    fused kernels (forward parity + grads vs the legacy oracle)."""
    c1, c2 = pair
    sp = init_params(c1, jax.random.PRNGKey(0))
    lg = init_ligo_params(jax.random.PRNGKey(1), c1, c2)
    plan = plan_for(c1, c2, sp)
    assert any(g.kernel_ok for g in plan.groups)
    if c1 is MOE1:
        assert any(g.kernel_ok and len(g.shape) == 4 for g in plan.groups), \
            "4-D MoE expert stacks must be fused-eligible"

    legacy = apply_ligo_reference(lg, sp, c1, c2)
    fused = plan.apply(lg, sp, use_kernel=True)
    for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    g_legacy = jax.grad(lambda l: _loss(l, lambda l: apply_ligo_reference(
        l, sp, c1, c2)))(lg)
    g_fused = jax.grad(lambda l: _loss(l, lambda l: plan.apply(
        l, sp, use_kernel=True)))(lg)
    _assert_grads_close(g_legacy, g_fused, rel=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_blend_expand_custom_vjp_matches_einsum_grad(use_kernel):
    """jax.grad through the custom_vjp == jax.grad through the plain einsum
    reference, for all three operands (w, B, W)."""
    rng = np.random.RandomState(0)
    L2, L1, D2, D1o, D1i = 4, 2, 128, 64, 128
    w = jnp.asarray(rng.randn(L2, L1), jnp.float32)
    B = jnp.asarray(rng.randn(D2, D1o) * 0.1, jnp.float32)
    W = jnp.asarray(rng.randn(L1, D1o, D1i) * 0.1, jnp.float32)

    def loss_fused(w, B, W):
        return jnp.sum(jnp.sin(
            ligo_blend_expand_vjp(w, B, W, use_kernel=use_kernel)))

    def loss_ref(w, B, W):
        return jnp.sum(jnp.sin(ligo_blend_expand_ref(w, B, W)))

    v, grads = jax.value_and_grad(loss_fused, argnums=(0, 1, 2))(w, B, W)
    vr, grads_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(w, B, W)
    np.testing.assert_allclose(float(v), float(vr), rtol=1e-5)
    for g, gr in zip(grads, grads_ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_plan_groups_cover_all_leaves_and_dedup_exprs(small_params):
    from repro.core.ligo import _flatten
    plan = plan_for(CFG1, CFG2, small_params)
    planned = sorted(
        (g.kind, p) for g in plan.groups for p in g.paths)
    expect = sorted(
        [(k, p) for k, st in small_params["layers"].items()
         for p in _flatten(st)]
        + [("", p) for p in _flatten(
            {k: v for k, v in small_params.items() if k != "layers"})])
    assert planned == expect
    # leaf batching: strictly fewer groups than leaves ...
    assert len(plan.groups) < len(planned)
    # ... and strictly fewer distinct expander resolutions than per-leaf
    # resolution would perform (2 per leaf in the legacy walk)
    assert len(plan.exprs) < len(planned)


def test_train_ligo_traces_once_and_resolves_once():
    """The LiGO phase compiles exactly once (lax.scan step, chunked) and
    resolves each distinct expander exactly once — at trace time, not per
    step."""
    cfg2 = CFG1.scaled(name="gp2t", n_layers=4)
    sp = init_params(CFG1, jax.random.PRNGKey(0))
    lg = init_ligo_params(jax.random.PRNGKey(1), CFG1, cfg2)
    plan = plan_for(CFG1, cfg2, sp)

    def batches():
        from repro.models.inputs import dummy_batch
        while True:
            yield dummy_batch(CFG1, 2, 16, "train")

    TRACE_COUNTS.clear()
    RESOLVE_COUNTS.clear()
    _, losses = train_ligo(lg, sp, CFG1, cfg2, batches(), steps=6,
                           scan_chunk=2)
    assert len(losses) == 6 and all(np.isfinite(losses))
    assert TRACE_COUNTS["train_ligo"] == 1, TRACE_COUNTS
    # one resolution per distinct (expr, role), counted once at trace time
    assert RESOLVE_COUNTS["resolve"] == len(plan.exprs), \
        (RESOLVE_COUNTS, len(plan.exprs))


def test_train_ligo_scan_matches_unchunked():
    """Chunked scan == one-shot scan (same numerics, carry donation safe),
    also with the carry checkpointed at every chunk boundary (the elastic
    phase)."""
    cfg2 = CFG1.scaled(name="gp2u", n_layers=4)
    sp = init_params(CFG1, jax.random.PRNGKey(0))
    lg = init_ligo_params(jax.random.PRNGKey(1), CFG1, cfg2)

    def batches():
        from repro.models.inputs import dummy_batch
        while True:
            yield dummy_batch(CFG1, 2, 16, "train")

    lg_a, loss_a = train_ligo(lg, sp, CFG1, cfg2, batches(), steps=4,
                              scan_chunk=2)
    lg_b, loss_b = train_ligo(lg, sp, CFG1, cfg2, batches(), steps=4)
    with tempfile.TemporaryDirectory() as d:
        lg_c, loss_c = train_ligo(lg, sp, CFG1, cfg2, batches(), steps=4,
                                  scan_chunk=2,
                                  phase_ckpt=CheckpointManager(d))
    for lg_x, loss_x in ((lg_a, loss_a), (lg_c, loss_c)):
        np.testing.assert_allclose(loss_x, loss_b, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(lg_x), jax.tree.leaves(lg_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
