"""Operation and byte counts from shapes, against hand counts and against
the dot operations XLA counts in the einsum references."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import flops  # noqa: E402
from benchmarks.chip.lib import harness as H  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks", "chip")


def _model(name, side):
    return H.load_json(os.path.join(BENCH, "configs", name))[side]


def test_bert_base_train_flops_per_token():
    # 6 x (12 layers x (4 x 768^2 + 2 x 768 x 3072) + 768 x 30522 head)
    # + 12 x 12 layers x 512 keys x 768 = 706,876,416 (about 707 MFLOP)
    m = _model("bert-small-to-base.json", "dst")
    assert flops.train_flops_per_token(m, 512) == 706_876_416


def test_gpt2_decode_round_by_hand():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "d_ff": 16,
         "vocab_size": 10, "causal": True}
    # matmul weights: 2 x (4 x 64 + 2 x 128) + 80 = 1104; per token 2208
    # operations, plus 4 x 2 layers x 8 x live length for attention
    assert flops.decode_flops(m, [3, 5]) == 2 * 2208 + 64 * 8
    # causal prefill of 4 tokens: 2 x 1024 x 4 + 2 x 80 + 4 x 2 x 8 x 10
    assert flops.prefill_flops(m, 4) == 8192 + 160 + 640


def _xla_flops(fn, *args):
    import jax
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


@pytest.mark.parametrize("G,L1,L2,E,I,A,Bd", [(1, 3, 5, 1, 12, 8, 16),
                                              (2, 2, 4, 3, 10, 6, 8)])
def test_fused_group_counts_match_einsum_reference(G, L1, L2, E, I, A, Bd):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    w = jax.ShapeDtypeStruct((G, L2, L1), jnp.float32)
    B = jax.ShapeDtypeStruct((I, A), jnp.float32)
    W = jax.ShapeDtypeStruct((G, L1, E, A, Bd), jnp.float32)
    dP = jax.ShapeDtypeStruct((G, L2, E, I, Bd), jnp.float32)
    ops_f, byts_f = flops.blend_expand_fwd(G, L1, L2, E, I, A, Bd, 4)
    ops_b, _ = flops.blend_expand_bwd(G, L1, L2, E, I, A, Bd, 4)
    assert ops_f == pytest.approx(
        _xla_flops(ref.ligo_blend_expand_grouped_ref, w, B, W), rel=1e-3)
    assert ops_b == pytest.approx(
        _xla_flops(ref.ligo_blend_expand_bwd_ref, w, B, W, dP), rel=1e-3)
    # every operand once: w, B (f32), W and the output
    assert byts_f == 4 * (G * L2 * L1 + I * A + G * L1 * E * A * Bd
                          + G * L2 * E * I * Bd)


def test_growth_count_is_least_order():
    """The reference's growth (expand, then blend in the grown space)
    needs at least the operations the least order counts."""
    import jax
    from benchmarks.chip.lib import reference as R
    from benchmarks.chip.lib import weights
    src = {"n_layers": 2, "d_model": 16, "n_heads": 2, "d_ff": 32,
           "vocab_size": 40, "max_seq": 8}
    dst = {"n_layers": 4, "d_model": 24, "n_heads": 3, "d_ff": 48,
           "vocab_size": 40, "max_seq": 8}
    small = jax.eval_shape(lambda k: weights.make_params(
        k, src, jax.numpy.float32), jax.random.PRNGKey(0))
    op = jax.eval_shape(lambda k: R.init_operator(k, src, dst),
                        jax.random.PRNGKey(0))
    xla = _xla_flops(lambda o, s: R.grow(o, s, src), op, small)
    least = flops.ligo_apply_flops(src, dst)
    assert 0 < least <= xla


def test_roofline_picks_the_binding_bound():
    assert flops.roofline_seconds(2e12, 1e9, 1e12, 1e11) == (2.0, "compute")
    assert flops.roofline_seconds(1e9, 1e12, 1e12, 1e11) == (10.0, "memory")
