"""Operator/plan composition: ``compose(A→B, B→C)`` must equal sequential
application for every growth method — the composed operator is an ordinary
LiGO tree, so a trajectory's stage-A→stage-C hop runs as a SINGLE fused
GrowthPlan (no intermediate model). Includes the hypothesis property over
random config triples and the ``gamma``/``seg``/``__in`` algebra edges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_trees_close_normalized

from repro.configs.paper_models import BERT_SMALL
from repro.core import (apply_ligo, compose_chain, compose_ligo,
                        init_ligo_params, plan_for)
from repro.core import operators as ops
from repro.models import init_params

# GQA triple (kv < heads at every hop) with constant d_head so the
# selection-copy baselines (stackbert/interpolation/net2net) apply too.
# Dims are kept small on purpose: the ≤1e-6 composed-vs-sequential bound is
# asserted in fp32, whose irreducible double-rounding noise grows ~√n with
# the contraction length (the f64 hypothesis property below checks the
# algebra itself at scale-independent precision).
C1 = BERT_SMALL.scaled(name="cp1", n_layers=2, d_model=16, n_heads=2,
                       n_kv_heads=1, d_head=8, d_ff=32, vocab_size=64,
                       max_seq=64, dtype="float32")
C2 = C1.scaled(name="cp2", n_layers=3, d_model=24, n_heads=3, n_kv_heads=1,
               d_ff=48)
C3 = C2.scaled(name="cp3", n_layers=5, d_model=32, n_heads=4, n_kv_heads=2,
               d_ff=64)
# width-only triple for net2net (its depth=None operator carries identity
# blends, valid only on depth-preserving hops)
W2 = C1.scaled(name="cpw2", d_model=48, n_heads=6, n_kv_heads=3, d_ff=96)
W3 = C1.scaled(name="cpw3", d_model=64, n_heads=8, n_kv_heads=4, d_ff=128)

METHODS = ("ligo", "stackbert", "interpolation", "net2net", "bert2bert")


def _operator(method, key, c1, c2):
    if method == "ligo":
        return init_ligo_params(key, c1, c2)
    if method == "stackbert":
        return ops.stackbert_operator(c1, c2, key=key)
    if method == "interpolation":
        return ops.interpolation_operator(c1, c2, key=key)
    if method == "net2net":
        return ops.net2net_operator(key, c1, c2)
    if method == "bert2bert":
        return ops.bert2bert_operator(key, c1, c2)
    raise ValueError(method)


def _triple(method):
    return (C1, W2, W3) if method == "net2net" else (C1, C2, C3)


def _names(tree):
    import jax.tree_util as jtu
    return ["/".join(str(getattr(k, "key", k)) for k in p)
            for p, _ in jtu.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("method", METHODS)
def test_composed_plan_matches_sequential(method):
    """The single fused A→C GrowthPlan fed the composed operator must match
    applying the two hops sequentially, ≤1e-6 (scale-normalized)."""
    c1, c2, c3 = _triple(method)
    sp = init_params(c1, jax.random.PRNGKey(0))
    op_a = _operator(method, jax.random.PRNGKey(1), c1, c2)
    op_b = _operator(method, jax.random.PRNGKey(2), c2, c3)

    mid = apply_ligo(op_a, sp, c1, c2, engine="legacy")
    want = apply_ligo(op_b, mid, c2, c3, engine="legacy")

    composed = compose_ligo(op_a, op_b, c1, c2, c3)
    got = plan_for(c1, c3, sp).executor()(composed, sp)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert_trees_close_normalized(got, want, rel=1e-6, names=_names(want))


def test_compose_chain_three_hops_and_identity():
    """compose_chain folds a whole trajectory; a single-hop chain passes
    through unchanged."""
    c4 = C3.scaled(name="cp4", n_layers=6, d_model=96, n_heads=12,
                   n_kv_heads=6, d_ff=192)
    chain = [C1, C2, C3, c4]
    sp = init_params(C1, jax.random.PRNGKey(0))
    op_list = [init_ligo_params(jax.random.PRNGKey(10 + i), a, b)
               for i, (a, b) in enumerate(zip(chain[:-1], chain[1:]))]

    cur = sp
    for op, a, b in zip(op_list, chain[:-1], chain[1:]):
        cur = apply_ligo(op, cur, a, b, engine="legacy")
    composed = compose_chain(op_list, chain)
    got = apply_ligo(composed, sp, C1, c4)
    assert_trees_close_normalized(got, cur, rel=2e-6, names=_names(cur))

    single = compose_chain([op_list[0]], [C1, C2])
    assert single is op_list[0]


def test_compose_squared_operator_consistency():
    """Second-moment semantics must survive composition for one-hot factor
    methods (the LEMON copy semantics): applying the composed operator with
    ``square=True`` equals squaring through the two hops sequentially —
    selection factors square to themselves and normalised fan-in squares
    multiply path-wise. Claimed for MHA only: GQA's ``gamma`` group
    averaging makes the single-hop and two-hop independence approximations
    legitimately differ (Σcᵢ² ≠ (Σcᵢ)² across an averaged group), and dense
    learned expanders differ for the same reason."""
    m1 = C1.scaled(name="cpm1", n_kv_heads=C1.n_heads)
    m2 = C2.scaled(name="cpm2", n_kv_heads=C2.n_heads)
    m3 = C3.scaled(name="cpm3", n_kv_heads=C3.n_heads)
    sp = init_params(m1, jax.random.PRNGKey(0))
    for mk in (ops.stackbert_operator,
               lambda a, b, key: ops.bert2bert_operator(key, a, b)):
        op_a = mk(m1, m2, key=jax.random.PRNGKey(1))
        op_b = mk(m2, m3, key=jax.random.PRNGKey(2))
        mid = apply_ligo(op_a, sp, m1, m2, engine="legacy", square=True)
        want = apply_ligo(op_b, mid, m2, m3, engine="legacy", square=True)
        composed = compose_ligo(op_a, op_b, m1, m2, m3)
        got = apply_ligo(composed, sp, m1, m3, engine="legacy", square=True)
        assert_trees_close_normalized(got, want, rel=1e-5,
                                      names=_names(want))


def test_compose_rejects_non_chaining_dims():
    op_a = init_ligo_params(jax.random.PRNGKey(1), C1, C2)
    op_bad = init_ligo_params(jax.random.PRNGKey(2), C1, C2)
    with pytest.raises((ValueError, AssertionError)):
        compose_ligo(op_a, op_bad, C1, C3, C3)


def test_compose_chain_validates_lengths():
    op = init_ligo_params(jax.random.PRNGKey(1), C1, C2)
    with pytest.raises(ValueError):
        compose_chain([op], [C1, C2, C3])
    with pytest.raises(ValueError):
        compose_chain([], [C1])


# ---------------------------------------------------------------------------
# Hypothesis: random config triples × all 5 methods
# ---------------------------------------------------------------------------
def test_compose_property_random_triples():
    """For random growable config triples, compose(A→B, B→C) matches
    sequential application ≤1e-6 (scale-normalized) for all 5 growth
    methods; net2net runs on the width-only projection of the triple.

    Both paths run in float64 (``enable_x64``): the claim under test is the
    *composition algebra* (gamma/seg/__in factor products, blend chaining),
    and in f64 its error sits at ~1e-15 — far below the 1e-6 bound — while
    fp32's irreducible double-rounding of the intermediate model would sit
    exactly AT the bound for the larger draws and turn the property into a
    noise test (the fp32 behaviour is pinned by the deterministic tests
    above at proxy dims)."""
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (optional dev dep)")
    from hypothesis import given, settings, strategies as st

    @given(h1=st.integers(1, 2), e1=st.integers(0, 2), e2=st.integers(0, 2),
           l1=st.integers(1, 2), d1=st.integers(0, 2), d2=st.integers(0, 2),
           f1=st.integers(1, 2), g1=st.integers(0, 1), g2=st.integers(0, 1),
           method=st.sampled_from(METHODS))
    @settings(max_examples=12, deadline=None)
    def run(h1, e1, e2, l1, d1, d2, f1, g1, g2, method):
        dh = 8
        h2, h3 = h1 + e1, h1 + e1 + e2
        if method == "net2net":
            d1 = d2 = 0                      # width-only chain
        c1 = BERT_SMALL.scaled(
            name="hc1", n_layers=l1, d_model=h1 * dh, n_heads=h1,
            n_kv_heads=h1, d_head=dh, d_ff=(f1 + g1) * h1 * dh,
            vocab_size=32, max_seq=32, dtype="float32")
        c2 = c1.scaled(name="hc2", n_layers=l1 + d1, d_model=h2 * dh,
                       n_heads=h2, n_kv_heads=h2,
                       d_ff=(f1 + g1 + g2) * h2 * dh)
        c3 = c2.scaled(name="hc3", n_layers=l1 + d1 + d2, d_model=h3 * dh,
                       n_heads=h3, n_kv_heads=h3,
                       d_ff=(f1 + g1 + g2 + 1) * h3 * dh)
        with jax.enable_x64(True):
            f64 = lambda t: jax.tree.map(  # noqa: E731
                lambda x: jnp.asarray(np.asarray(x), jnp.float64), t)
            sp = f64(init_params(c1, jax.random.PRNGKey(0)))
            op_a = f64(_operator(method, jax.random.PRNGKey(1), c1, c2))
            op_b = f64(_operator(method, jax.random.PRNGKey(2), c2, c3))
            mid = apply_ligo(op_a, sp, c1, c2, engine="legacy")
            want = apply_ligo(op_b, mid, c2, c3, engine="legacy")
            got = apply_ligo(compose_ligo(op_a, op_b, c1, c2, c3), sp,
                             c1, c3, engine="legacy")
            assert_trees_close_normalized(got, want, rel=1e-6,
                                          names=_names(want))

    run()
