"""Config registry invariants: exact assigned dims, cell enumeration,
analytic param counts vs real initialisation."""
import jax
import pytest

from repro.configs import (ASSIGNED, enumerate_cells, get_config, grow_target,
                           half_config, smoke_config)
from repro.models import init_params


EXPECTED_DIMS = {
    # arch: (L, d_model, H, KV, d_ff, vocab)
    "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
    "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
    "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
    "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
    "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
    "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
    "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
    "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
}


@pytest.mark.parametrize("arch", sorted(EXPECTED_DIMS))
def test_assigned_dims_exact(arch):
    cfg = get_config(arch)
    L, D, H, KV, FF, V = EXPECTED_DIMS[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (L, D, H, KV, FF, V)


def test_cell_enumeration_counts():
    cells = enumerate_cells()
    assert len(cells) == 40                       # 10 archs × 4 shapes
    runnable = [c for c in cells if c.runnable]
    skipped = [c for c in cells if not c.runnable]
    assert len(runnable) == 33 and len(skipped) == 7
    skip_keys = {c.key for c in skipped}
    assert "hubert-xlarge/decode_32k" in skip_keys
    assert "hubert-xlarge/long_500k" in skip_keys
    for a in ("llama3-8b", "phi4-mini-3.8b", "deepseek-coder-33b",
              "qwen3-moe-30b-a3b", "qwen2-vl-72b"):
        assert f"{a}/long_500k" in skip_keys
    # sub-quadratic archs DO run long_500k (StarCoder2: sliding window)
    for a in ("mixtral-8x7b", "xlstm-125m", "zamba2-2.7b", "starcoder2-7b"):
        assert f"{a}/long_500k" not in skip_keys


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_param_count_matches_init(arch):
    """The analytic 6ND param count must equal the real init's size."""
    cfg = smoke_config(ASSIGNED[arch])
    params = init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert cfg.param_count() == actual, (cfg.param_count(), actual)


def test_full_param_counts_plausible():
    """Sanity: headline parameter counts land near the public numbers."""
    expect = {"llama3-8b": (7.5e9, 9.0e9),
              "deepseek-coder-33b": (31e9, 35e9),
              "mixtral-8x7b": (44e9, 49e9),
              "qwen2-vl-72b": (68e9, 76e9),
              # our xLSTM uses full d×d recurrent matrices (official uses
              # block-diagonal) so it lands a bit heavy
              "xlstm-125m": (0.10e9, 0.25e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_half_and_grow_configs_are_growable():
    from repro.core.spec import check_growable
    for arch, cfg in ASSIGNED.items():
        small = half_config(cfg)
        check_growable(small, cfg)
        s = smoke_config(cfg)
        check_growable(s, grow_target(s))


@pytest.mark.parametrize("arch,n_tied,n_untied", [
    ("starcoder2-3b", 3_030_371_328, 3_181_366_272),
    ("starcoder2-7b", 7_173_923_840, 7_400_416_256),
])
def test_starcoder2_published_block(arch, n_tied, n_untied):
    """StarCoder2 as published: LayerNorm eps 1e-5 with biases, tanh-GELU,
    GQA at head size 128, RoPE, a 4096 window over 16384 positions, and a
    tied head (the tied count is the size BigCode gives)."""
    cfg = get_config(arch)
    assert (cfg.norm, cfg.norm_eps, cfg.act, cfg.rope, cfg.d_head,
            cfg.window, cfg.max_seq, cfg.tie_embeddings) == (
        "layer", 1e-5, "gelu", "rope", 128, 4096, 16384, True)
    assert cfg.param_count() == n_tied
    assert cfg.scaled(tie_embeddings=False).param_count() == n_untied


def test_starcoder2_3b_grows_into_7b():
    """The published 3B is a growth source for the 7B: group size 12 -> 9,
    kv width 256 -> 512, and only the assigned archs count as cells."""
    from repro.core.spec import check_growable
    src, dst = get_config("starcoder2-3b"), get_config("starcoder2-7b")
    check_growable(src, dst)
    assert (src.n_heads // src.n_kv_heads, dst.n_heads // dst.n_kv_heads) \
        == (12, 9)
    assert (src.kv_dim, dst.kv_dim) == (256, 512)
    assert "starcoder2-3b" not in ASSIGNED


def test_norm_eps_reaches_every_norm():
    """``norm_eps`` is the epsilon of the model's norms: a LayerNorm model
    whose eps changes gives other hidden states (default 1e-6 elsewhere)."""
    from repro.models import forward
    cfg = smoke_config(get_config("starcoder2-7b"))
    assert get_config("llama3-8b").norm_eps == 1e-6
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                          cfg.vocab_size)}
    a = forward(params, cfg, batch)[0]
    b = forward(params, cfg.scaled(norm_eps=1.0), batch)[0]
    assert float(jax.numpy.max(jax.numpy.abs(a - b))) > 1e-3
