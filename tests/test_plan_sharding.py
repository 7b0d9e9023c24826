"""Sharded GrowthPlan: ``executor(mesh=...)`` must reproduce the unsharded
plan bit-for-tolerance (≤1e-6 rel) for every growth method on 1/2/4/8-device
host meshes, grown leaves must land carrying exactly the ``NamedSharding``
that ``distributed.sharding.params_pspecs`` prescribes, the fused Pallas
route must survive its ``shard_map`` wrapping (values + grads), and the
plan's spec derivation must stay consistent with the real parameter trees
under random config pairs (hypothesis).

Mesh-parametrized cases run fully on the forced-8-virtual-device CI lane
(REPRO_FORCE_HOST_DEVICES=8) and degrade to the 1-device cases elsewhere;
an end-to-end subprocess smoke for the single-device lane lives in
tests/test_distributed.py.
"""
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from conftest import assert_trees_close_normalized
from test_growth_plan import CFG1, CFG2, METHODS, _operator

from repro.core import apply_ligo, init_ligo_params, plan_for
from repro.core.ligo import _flatten
from repro.distributed.sharding import named_shardings
from repro.models import init_params

MESHES = [
    ((1,), ("data",)),
    ((2,), ("data",)),
    ((2, 2), ("data", "model")),
    ((2, 4), ("data", "model")),
]
MESH_IDS = ["1dev", "2dev", "2x2", "2x4"]


@pytest.fixture(scope="module")
def small_params():
    return init_params(CFG1, jax.random.PRNGKey(0))


@pytest.mark.parametrize("mesh_def", MESHES, ids=MESH_IDS)
@pytest.mark.parametrize("method", METHODS)
def test_sharded_apply_parity(mesh_factory, small_params, method, mesh_def):
    """executor(mesh=...) == unsharded executor for every growth operator:
    the pjit program (in/out shardings, per-group constraints) must not
    change the numerics of any contraction."""
    mesh = mesh_factory(*mesh_def)
    op = _operator(method)
    plan = plan_for(CFG1, CFG2, small_params)
    want = plan.executor()(op, small_params)
    got = plan.executor(mesh=mesh)(op, small_params)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    flat = jtu.tree_flatten_with_path(want)[0]
    names = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in flat]
    assert_trees_close_normalized(got, want, rel=1e-6, names=names)


def test_output_leaves_carry_prescribed_shardings(mesh_factory, small_params):
    """Every grown leaf lands with the NamedSharding params_pspecs prescribes
    for the large model's weights — ready for the sharded train step with no
    resharding — and at least some leaves are genuinely partitioned."""
    mesh = mesh_factory((2, 4), ("data", "model"))
    op = _operator("ligo")
    plan = plan_for(CFG1, CFG2, small_params)
    big = plan.executor(mesh=mesh)(op, small_params)
    _, big_ps = plan.pspecs(mesh)
    want_sh = named_shardings(big_ps, mesh)
    assert jax.tree.structure(big) == jax.tree.structure(want_sh)
    partitioned = 0
    for (path, leaf), sh in zip(jtu.tree_flatten_with_path(big)[0],
                                jax.tree.leaves(want_sh)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), \
            (path, leaf.sharding, sh)
        partitioned += not leaf.sharding.is_fully_replicated
    assert partitioned > 0, "no leaf actually partitioned on an 8-way mesh"


@pytest.mark.parametrize("mesh_def", [((2,), ("data",)),
                                      ((2, 4), ("data", "model"))],
                         ids=["2dev", "2x4"])
def test_sharded_fused_path_matches_legacy(mesh_factory, small_params,
                                           mesh_def):
    """use_kernel=True under a mesh routes eligible groups through the
    grouped custom_vjp inside shard_map (per-shard Pallas interpret mode on
    CPU) — values and all operator gradients must match the legacy walk."""
    mesh = mesh_factory(*mesh_def)
    op = _operator("ligo")
    plan = plan_for(CFG1, CFG2, small_params)
    assert any(g.kernel_ok for g in plan.groups)

    legacy = apply_ligo(op, small_params, CFG1, CFG2, engine="legacy")
    fused = plan.apply(op, small_params, use_kernel=True, mesh=mesh)
    for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def loss(l, fn):
        return sum(jnp.sum(x * x) for x in jax.tree.leaves(fn(l)))

    g_legacy = jax.grad(lambda l: loss(l, lambda l: apply_ligo(
        l, small_params, CFG1, CFG2, engine="legacy")))(op)
    g_fused = jax.grad(lambda l: loss(l, lambda l: plan.apply(
        l, small_params, use_kernel=True, mesh=mesh)))(op)
    assert_trees_close_normalized(g_fused, g_legacy, rel=1e-5)


def test_ambient_mesh_auto_pickup(mesh_factory, small_params):
    """apply_ligo with no mesh argument grows sharded under set_mesh — the
    plumbing the train/serve drivers rely on."""
    mesh = mesh_factory((2, 4), ("data", "model"))
    op = _operator("ligo")
    plan = plan_for(CFG1, CFG2, small_params)
    want = plan.executor()(op, small_params)
    with jax.set_mesh(mesh):
        got = apply_ligo(op, small_params, CFG1, CFG2)
    assert_trees_close_normalized(got, want, rel=1e-6)
    assert any(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(got))


# ---------------------------------------------------------------------------
# Spec-derivation consistency under random config pairs (device-free)
# ---------------------------------------------------------------------------
def _check_specs_valid(shape_tree, spec_tree, sizes):
    """Every spec entry must have full rank and every named axis (subset)
    must divide the dim it shards."""
    flat_shapes = _flatten(shape_tree)
    flat_specs = _flatten(spec_tree)
    assert sorted(flat_shapes) == sorted(flat_specs)
    for path, spec in flat_specs.items():
        shape = flat_shapes[path].shape
        assert len(spec) == len(shape), (path, spec, shape)
        for dim, entry in zip(shape, spec):
            if entry is None:
                continue
            prod = 1
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                prod *= sizes.get(ax, 1)
            assert dim % prod == 0, (path, spec, shape)


def test_plan_spec_consistency_property():
    """Hypothesis: for random growable config pairs, the plan's rebuilt
    small/big trees match the real parameter trees exactly (structure +
    shapes == eval_shape of apply), and the derived PartitionSpecs are valid
    (full-rank, divisibility) for every leaf and for every group's stacked
    constraint, across several mesh factorizations."""
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (optional dev dep)")
    from types import SimpleNamespace

    from hypothesis import given, settings, strategies as st

    from repro.configs.paper_models import BERT_SMALL
    from repro.core.ligo import _kind_counts

    @given(dh=st.sampled_from([4, 8]), h1=st.integers(1, 3),
           dh_extra=st.integers(0, 3), l1=st.integers(1, 3),
           dl=st.integers(0, 4), fm1=st.integers(1, 2),
           fm_extra=st.integers(0, 2))
    @settings(max_examples=10, deadline=None)
    def run(dh, h1, dh_extra, l1, dl, fm1, fm_extra):
        h2 = h1 + dh_extra
        cfg1 = BERT_SMALL.scaled(
            name="hp1", n_layers=l1, d_model=h1 * dh, n_heads=h1,
            n_kv_heads=h1, d_head=dh, d_ff=fm1 * h1 * dh, vocab_size=32,
            max_seq=32, dtype="float32")
        cfg2 = cfg1.scaled(
            name="hp2", n_layers=l1 + dl, d_model=h2 * dh, n_heads=h2,
            n_kv_heads=h2, d_ff=(fm1 + fm_extra) * h2 * dh)
        sp = jax.eval_shape(
            lambda: init_params(cfg1, jax.random.PRNGKey(0)))
        lg = jax.eval_shape(
            lambda: init_ligo_params(jax.random.PRNGKey(0), cfg1, cfg2))
        plan = plan_for(cfg1, cfg2, sp)
        big = jax.eval_shape(plan.apply, lg, sp)

        small_t, big_t = plan._abstract_trees()
        shape_of = lambda t: jax.tree.map(lambda x: x.shape, t)  # noqa: E731
        assert shape_of(small_t) == shape_of(sp)
        assert shape_of(big_t) == shape_of(big)

        c2 = _kind_counts(cfg2)
        for model_sz, dp_sz in ((1, 1), (2, 2), (4, 2)):
            sizes = {"model": model_sz, "data": dp_sz}
            mesh = SimpleNamespace(shape=sizes)
            small_ps, big_ps = plan.pspecs(mesh)
            _check_specs_valid(sp, small_ps, sizes)
            _check_specs_valid(big, big_ps, sizes)
            # group constraints: first leaf's spec must be valid for the
            # whole (G, ...) stack, i.e. all leaves of a group share shapes
            flat_specs = {kind: _flatten(stack)
                          for kind, stack in big_ps["layers"].items()}
            flat_specs[""] = _flatten({k: v for k, v in big_ps.items()
                                       if k != "layers"})
            for g in plan.groups:
                out_shape = plan._out_shape(g, c2.get(g.kind, 0))
                spec = flat_specs[g.kind][g.paths[0]]
                for p in g.paths:
                    got = flat_specs[g.kind][p]
                    assert len(got) == len(out_shape), (g.kind, p)
                stacked = (len(g.paths),) + out_shape
                for dim, entry in zip(stacked, (None,) + tuple(spec)):
                    if entry is None:
                        continue
                    prod = 1
                    for ax in (entry if isinstance(entry, tuple)
                               else (entry,)):
                        prod *= sizes.get(ax, 1)
                    assert dim % prod == 0, (g.kind, g.paths, spec)

    run()
