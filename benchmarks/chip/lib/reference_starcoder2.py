"""The plain reference for growing StarCoder2: the model, its causal-LM
loss, LiGO growth with GQA, and SGD with momentum, in straightforward
``jax.numpy`` at float32 and ``highest`` precision.

It imports nothing of the program and is given only what the harness made
from the seed (weights, AdamW state, batches). The block follows StarCoder2
(Lozhkov et al. 2024, arXiv:2402.19173; ``bigcode/starcoder2-3b`` and
``-7b`` config.json):

- pre-norm LayerNorm with bias, eps ``norm_eps`` (1e-5);
- q, k, v, o projections with biases; grouped-query attention, query head
  ``h`` reading kv head ``h // (n_heads / n_kv_heads)``;
- RoPE on q and k, rotating the two halves of each head (NeoX layout),
  inverse frequencies ``theta^(-2i / d_head)``;
- causal sliding-window attention: a query at position t attends to the
  keys at positions t - window + 1 .. t;
- a biased ``c_fc -> gelu(tanh) -> c_proj`` MLP, a final LayerNorm and a
  head tied to the token embedding;
- the causal LM loss: mean cross entropy of every next token
  (``targets``) over every position of every row.

LiGO (Wang et al. 2023, Eq. 8) with this repository's tying for GQA: per
leaf ``Omega'_k = sum_j w[k, j] (E_in W_j E_out^T)`` with
``A^{Q,K,V} = B_emb``, ``A^{fc1} = B_emb``, ``A^{fc2} = B_fc``,
``B^O = B^{fc2} = B_emb``, and ``A^O = Gamma(B_v)``: the output projection
reads the query-head space, and ``Gamma`` lifts the kv-space expander to it,
sending a source query head to a target query head through ``B_v``'s block
between their kv heads, averaged over the ``G1`` source query heads that
share a kv head:
``Gamma[(h2, i), (h1, j)] = B_v[(h2 // G2, i), (h1 // G1, j)] / G1``.
Norms and biases grow through their module's out-expander. The operator
starts from the stacking pattern and noisy identity-plus-copied-rows
expanders drawn from the seed. The second moment grows through the
elementwise square of every expander and blend, taken after ``Gamma``.

Departures from the published model, each shared with the program: the
weights are random; the model is the first stage of a pipeline (the first
``n_layers`` blocks with the embedding, final norm and tied head), and the
depth blend mixes only that stage's source layers. To fit four chips, the
forward runs layer by layer, each layer recomputed in the backward pass,
and attention runs over blocks of queries; under a mesh, activations and
grown matrices are laid over it by sharding constraints (batch over
``data``, heads and the trailing dim of a matrix over ``model``), which
change where the numbers are computed and not what they are.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.chip.lib.reference import (CONTROL, F32, REF, Precision,
                                           cross_entropy, gelu,
                                           sgd_momentum)

__all__ = ["REF", "CONTROL", "BF16", "logits", "clm_loss", "ligo_loss",
           "init_operator", "grow", "sgd_momentum", "gamma"]

Q_BLOCK = 1024          # queries per attention block


@dataclass(frozen=True)
class Cast(Precision):
    """Matrix-product operands rounded to a float type (bf16 shows that the
    comparison catches a reference computed a step below float32)."""
    name: str = "bf16"

    def q(self, x):
        x = x.astype(F32)
        r = x.astype(jnp.bfloat16).astype(F32)
        return x + jax.lax.stop_gradient(r - x)


BF16 = Cast()


def constrain(x, *spec):
    """Lay ``x`` over the ambient mesh (a no-op without one); an axis the
    mesh lacks, or that does not divide its dimension, is left whole."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    sizes = dict(mesh.shape)
    spec = [a if a in sizes and x.shape[i] % sizes[a] == 0 else None
            for i, a in enumerate(spec)]
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _matrix(x, lead: int):
    """A grown leaf laid out as the chips would hold it: a matrix (after
    ``lead`` stacked dims) with its trailing dim over ``model`` and the one
    before over ``data``; vectors whole."""
    if x.ndim - lead != 2:
        return x
    return constrain(x, *([None] * lead + ["data", "model"]))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------
def layer_norm(p, x, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def rope(x, theta: float):
    """x (B, T, heads, dh): rotate the halves (x1, x2) of every head by the
    angle position * theta^(-2i / dh)."""
    T, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv              # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int, pr: Precision):
    """q (B, T, H, dh), k and v (B, T, KV, dh) -> (B, T, H, dh): each query
    head over its kv head's keys in (t - window, t], a block of queries at
    a time."""
    T, H, dh = q.shape[1:]
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    k = constrain(k, "data", None, "model", None)
    v = constrain(v, "data", None, "model", None)
    out = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(T, lo + Q_BLOCK)
        s = pr.mm("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) / math.sqrt(dh)
        s = constrain(s, "data", "model", None, None)
        t = jnp.arange(lo, hi)[:, None]
        u = jnp.arange(hi)[None, :]
        keep = (u <= t) & (u > t - window) if window else (u <= t)
        a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        out.append(pr.mm("bhqk,bkhd->bqhd", a, v[:, :hi]))
    return jnp.concatenate(out, axis=1)


def block(p, x, m: Dict, pr: Precision):
    """One pre-norm StarCoder2 layer on x (B, T, d), float32."""
    B, T, _ = x.shape
    H, KV = m["n_heads"], m["n_kv_heads"]
    dh, eps = m["d_head"], m["norm_eps"]
    f = lambda n: p[n].astype(F32)                                # noqa: E731
    h = layer_norm(p["ln1"], x, eps)
    q = (pr.mm("btd,de->bte", h, p["wq"]) + f("bq")).reshape(B, T, H, dh)
    k = (pr.mm("btd,de->bte", h, p["wk"]) + f("bk")).reshape(B, T, KV, dh)
    v = (pr.mm("btd,de->bte", h, p["wv"]) + f("bv")).reshape(B, T, KV, dh)
    q = constrain(rope(q, m["rope_theta"]), "data", None, "model", None)
    k = rope(k, m["rope_theta"])
    o = attention(q, k, v, m.get("window", 0), pr).reshape(B, T, H * dh)
    x = x + pr.mm("bte,ed->btd", o, p["wo"]) + f("bo")
    x = constrain(x, "data", None, None)
    h2 = layer_norm(p["ln2"], x, eps)
    mlp = p["mlp"]
    u = gelu(pr.mm("btd,df->btf", h2, mlp["w1"]) + mlp["b1"].astype(F32))
    u = constrain(u, "data", None, "model")
    x = x + pr.mm("btf,fd->btd", u, mlp["w2"]) + mlp["b2"].astype(F32)
    return constrain(x, "data", None, None)


def hidden(params, m: Dict, tokens, pr: Precision = REF):
    """Final-normed hidden states (B, T, d), float32, a layer at a time
    (a scan over the stacked layers), each layer's activations recomputed
    in the backward pass."""
    x = jnp.take(params["embed"]["tok"].astype(F32), tokens, axis=0)
    x = constrain(x, "data", None, None)
    layer = jax.checkpoint(lambda x, p: (block(p, x, m, pr), None))
    x, _ = jax.lax.scan(layer, x, params["layers"]["attn"])
    return layer_norm(params["final_norm"], x, m["norm_eps"])


def logits(params, m: Dict, tokens, pr: Precision = REF):
    """(B, T, vocab) through the head tied to the token embedding."""
    lg = pr.mm("btd,vd->btv", hidden(params, m, tokens, pr),
               params["embed"]["tok"])
    return constrain(lg, "data", None, "model")


def clm_loss(params, m: Dict, batch, pr: Precision = REF):
    """Mean cross entropy of every next token."""
    return jnp.mean(cross_entropy(logits(params, m, batch["tokens"], pr),
                                  batch["targets"]))


# ---------------------------------------------------------------------------
# LiGO
# ---------------------------------------------------------------------------
LAYER_SPEC = {
    "ln1/scale": (None, "emb"), "ln1/bias": (None, "emb"),
    "ln2/scale": (None, "emb"), "ln2/bias": (None, "emb"),
    "wq": ("emb", "q"), "bq": (None, "q"),
    "wk": ("emb", "k"), "bk": (None, "k"),
    "wv": ("emb", "v"), "bv": (None, "v"),
    "wo": ("gamma", "emb"), "bo": (None, "emb"),      # A^O = Gamma(B_v)
    "mlp/w1": ("emb", "fc"), "mlp/b1": (None, "fc"),
    "mlp/w2": ("fc", "emb"), "mlp/b2": (None, "emb"),
}
TOP_SPEC = {
    "embed/tok": (None, "emb"),
    "final_norm/scale": (None, "emb"), "final_norm/bias": (None, "emb"),
}


def width_dims(m: Dict) -> Dict[str, int]:
    """The expanders' spaces: model width, query heads, kv heads (k and v),
    feed-forward."""
    dh = m["d_head"]
    return {"emb": m["d_model"], "q": m["n_heads"] * dh,
            "k": m["n_kv_heads"] * dh, "v": m["n_kv_heads"] * dh,
            "fc": m["d_ff"]}


def init_operator(key, src: Dict, dst: Dict, noise: float = 0.01) -> Dict:
    """The operator LiGO starts from: each expander [I; one-hot copies of
    random source rows] + noise (drawn per expander name, in sorted order),
    and depth blends that stack (target layer k copies source layer
    k mod L1)."""
    d1, d2 = width_dims(src), width_dims(dst)
    keys = jax.random.split(key, len(d2) + 1)
    width = {}
    for i, name in enumerate(sorted(d2)):
        k1, k2 = jax.random.split(keys[i])
        a, b = d2[name], d1[name]
        eye = jnp.eye(a, b)
        if a > b:
            rows = jax.random.randint(k1, (a - b,), 0, b)
            eye = jnp.concatenate([jnp.eye(b), jax.nn.one_hot(rows, b)])
        width[name] = eye + noise * jax.random.normal(k2, (a, b))
    L1, L2 = src["n_layers"], dst["n_layers"]
    stack = jax.nn.one_hot(jnp.arange(L2) % L1, L1)
    return {"width": width, "depth": {"attn": {leaf: stack
                                               for leaf in LAYER_SPEC}}}


def gamma(Bv, src: Dict, dst: Dict):
    """Gamma(B_v): (H2 dh2, H1 dh1), target query head h2 from source query
    head h1 through B_v's (h2 // G2, h1 // G1) block, over G1."""
    H1, KV1, dh1 = src["n_heads"], src["n_kv_heads"], src["d_head"]
    H2, KV2, dh2 = dst["n_heads"], dst["n_kv_heads"], dst["d_head"]
    G1, G2 = H1 // KV1, H2 // KV2
    blocks = Bv.astype(F32).reshape(KV2, dh2, KV1, dh1)
    lifted = blocks[jnp.arange(H2) // G2][:, :, jnp.arange(H1) // G1]
    return lifted.reshape(H2 * dh2, H1 * dh1) / G1


def _get(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _put(tree, path, value):
    ks = path.split("/")
    for k in ks[:-1]:
        tree = tree.setdefault(k, {})
    tree[ks[-1]] = value


def grow(op: Dict, small: Dict, src: Dict, dst: Dict,
         pr: Precision = REF, *, square: bool = False) -> Dict:
    """The grown tree, float32. ``square`` grows through the elementwise
    square of every expander and blend (the AdamW second-moment map)."""
    width = op["width"]
    table = {n: width[n].astype(F32) for n in width_dims(dst)}
    table["gamma"] = gamma(width["v"], src, dst)
    if square:
        table = {n: e * e for n, e in table.items()}

    def leaf(W, w, e_in, e_out):
        """E_in W E_out^T, blended over depth by ``w`` when given."""
        W = W.astype(F32)
        if W.ndim == 1:
            return pr.mm("ja,a->j", table[e_out], W)
        if e_in is not None:
            W = pr.mm("ia,...ab->...ib", table[e_in], W)
        if e_out is not None:
            W = pr.mm("...ab,jb->...aj", W, table[e_out])
        return W if w is None else pr.mm("kl,l...->k...", w, W)

    # each leaf's growth is recomputed in the backward pass, not kept
    leaf = jax.checkpoint(leaf, static_argnums=(2, 3))
    out: Dict = {}
    stack = small["layers"]["attn"]
    for path, (e_in, e_out) in LAYER_SPEC.items():
        w = op["depth"]["attn"][path].astype(F32)
        wide = leaf(_get(stack, path), w * w if square else w, e_in, e_out)
        _put(out, "layers/attn/" + path, _matrix(wide, 1))
    for path, (e_in, e_out) in TOP_SPEC.items():
        _put(out, path, _matrix(leaf(_get(small, path), None, e_in, e_out),
                                0))
    return out


def ligo_loss(op, small, src: Dict, dst: Dict, batch, pr: Precision = REF):
    return clm_loss(grow(op, small, src, dst, pr), dst, batch, pr)
