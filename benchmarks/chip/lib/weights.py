"""Weights, AdamW state and batches made on the device from the seed.

Each maker is one jitted call from a key, so a run pays one dispatch per
tree instead of one per leaf. The trees follow the program's parameter
layout for a dense pre-norm transformer with biases (layer leaves stacked
over a leading layer axis), which is the interface the program takes them
through; their values are this benchmark's own.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
STD = 0.02          # BERT's initializer range


def _normal(key, shape, dtype, std=STD):
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)
            ).astype(dtype)


def make_params(key, m: Dict, dtype) -> Dict:
    """A transformer's parameter tree (traced; call under ``jax.jit``)."""
    L, d, ff, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    ks = iter(jax.random.split(key, 32))

    def norm(lead=()):
        return {"scale": (1.0 + _normal(next(ks), lead + (d,), jnp.float32)
                          ).astype(dtype),
                "bias": _normal(next(ks), lead + (d,), dtype)}

    layers = {"ln1": norm((L,)), "ln2": norm((L,)),
              "wq": _normal(next(ks), (L, d, d), dtype),
              "wk": _normal(next(ks), (L, d, d), dtype),
              "wv": _normal(next(ks), (L, d, d), dtype),
              "wo": _normal(next(ks), (L, d, d), dtype),
              "bq": _normal(next(ks), (L, d), dtype),
              "bk": _normal(next(ks), (L, d), dtype),
              "bv": _normal(next(ks), (L, d), dtype),
              "bo": _normal(next(ks), (L, d), dtype),
              "mlp": {"w1": _normal(next(ks), (L, d, ff), dtype),
                      "w2": _normal(next(ks), (L, ff, d), dtype),
                      "b1": _normal(next(ks), (L, ff), dtype),
                      "b2": _normal(next(ks), (L, d), dtype)}}
    params = {"embed": {"tok": _normal(next(ks), (V, d), dtype),
                        "pos": _normal(next(ks), (m["max_seq"], d), dtype)},
              "layers": {"attn": layers}, "final_norm": norm()}
    if not m.get("tie_embeddings", False):
        params["head"] = _normal(next(ks), (d, V), dtype)
    return params


def make_moments(key, params, *, m_std: float = 1e-4) -> Dict:
    """AdamW moments of a run under way (float32): m ~ N(0, m_std^2) and
    v = m^2 + (m_std / 2)^2, so that every update is of order the
    learning rate (traced; call under ``jax.jit``)."""
    leaves, tdef = jax.tree.flatten(params)
    ks = jax.random.split(key, len(leaves))
    ms = [m_std * jax.random.normal(k, x.shape, jnp.float32)
          for k, x in zip(ks, leaves)]
    vs = [x * x + (m_std / 2) ** 2 for x in ms]
    return tdef.unflatten(ms), tdef.unflatten(vs)


def params_on_device(key, m: Dict, dtype):
    return jax.jit(lambda k: make_params(k, m, dtype))(key)


def moments_on_device(key, params):
    return jax.jit(make_moments)(key, params)

