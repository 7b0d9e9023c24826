"""The plain reference: a pre-norm transformer, LiGO growth, SGD with
momentum and AdamW, in straightforward ``jax.numpy``.

It imports nothing of the program and is given only what the harness made
from the seed (weights, AdamW state, batches). It follows the published
descriptions:

- the transformer of BERT (Devlin et al. 2019) and GPT-2 (Radford et al.
  2019) as this repository lays it out: learned positions, pre-norm
  layers (LayerNorm, eps 1e-6), biased projections, tanh-GELU MLP, a final
  norm; BERT's masked-LM loss over masked positions, GPT-2's causal LM with
  the head tied to the embedding;
- LiGO (Wang et al. 2023, Eq. 8 and Alg. 1): per leaf
  Omega'_k = sum_j w[k, j] (E_in W_j E_out^T), with the tying
  A^{Q,K,V} = B_emb, A^O = B_v^T, B^O = B_emb, A^{fc1} = B_emb,
  A^{fc2} = B_fc1, B^{fc2} = B_emb, norms and biases through their module's
  out-expander; the operator starts from the stacking pattern (layer k
  copies layer k mod L1) and noisy identity-plus-copied-rows expanders;
- AdamW (Loshchilov & Hutter 2019) with bias correction, decay on matrices
  only, after global-norm clipping, and the warm-up-then-cosine schedule.

``Precision`` says how each matrix product rounds its operands: float32 at
``highest`` is the reference; the control rounds them to 8-bit floats
(e4m3, one scale per tensor), the step below the bfloat16 the
configurations state. Softmax, norms and the loss stay in float32 either
way.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6


@dataclass(frozen=True)
class Precision:
    name: str = "f32"              # "f32" (reference) or "fp8" (control)

    def q(self, x):
        """A matrix-product operand as this precision sees it."""
        x = x.astype(F32)
        if self.name == "f32":
            return x
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
        r = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
        return x + jax.lax.stop_gradient(r - x)

    def mm(self, spec: str, a, b):
        with jax.default_matmul_precision("highest"):
            return jnp.einsum(spec, self.q(a), self.q(b),
                              preferred_element_type=F32)


REF = Precision("f32")
CONTROL = Precision("fp8")


# ---------------------------------------------------------------------------
# The transformer
# ---------------------------------------------------------------------------
def layer_norm(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def block(p, x, m: Dict, pr: Precision, causal: bool):
    """One pre-norm layer on x (B, T, d), float32."""
    B, T, _ = x.shape
    H = m["n_heads"]
    f = lambda n: p[n].astype(F32)                                # noqa: E731
    h = layer_norm(p["ln1"], x)
    q = pr.mm("btd,de->bte", h, p["wq"]) + f("bq")
    k = pr.mm("btd,de->bte", h, p["wk"]) + f("bk")
    v = pr.mm("btd,de->bte", h, p["wv"]) + f("bv")
    dh = q.shape[-1] // H
    q, k, v = (t.reshape(B, T, H, dh) for t in (q, k, v))
    s = pr.mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = pr.mm("bhqk,bkhd->bqhd", a, v).reshape(B, T, H * dh)
    x = x + pr.mm("bte,ed->btd", o, p["wo"]) + f("bo")
    h2 = layer_norm(p["ln2"], x)
    mlp = p["mlp"]
    u = gelu(pr.mm("btd,df->btf", h2, mlp["w1"]) + mlp["b1"].astype(F32))
    return x + pr.mm("btf,fd->btd", u, mlp["w2"]) + mlp["b2"].astype(F32)


def hidden(params, m: Dict, tokens, pr: Precision = REF):
    """Final-normed hidden states (B, T, d), float32, layer by layer with
    each layer's activations recomputed in the backward pass."""
    T = tokens.shape[1]
    emb = params["embed"]
    x = (jnp.take(emb["tok"].astype(F32), tokens, axis=0)
         + emb["pos"].astype(F32)[:T])
    causal = bool(m.get("causal", False))
    layer = jax.checkpoint(lambda p, x: block(p, x, m, pr, causal))
    stack = params["layers"]["attn"]
    for i in range(m["n_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], stack), x)
    return layer_norm(params["final_norm"], x)


def head_matrix(params, m: Dict):
    """(d, V): the output head, or the embedding transposed when tied."""
    if m.get("tie_embeddings", False):
        return params["embed"]["tok"].T
    return params["head"]


def logits(params, m: Dict, tokens, pr: Precision = REF):
    return pr.mm("btd,dv->btv", hidden(params, m, tokens, pr),
                 head_matrix(params, m))


def cross_entropy(lg, labels):
    return (jax.nn.logsumexp(lg, -1)
            - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0])


def mlm_loss(params, m: Dict, batch, pr: Precision = REF):
    """Mean cross entropy over the masked positions."""
    ce = cross_entropy(logits(params, m, batch["tokens"], pr),
                       batch["labels"])
    w = batch["mask"].astype(F32)
    return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# LiGO
# ---------------------------------------------------------------------------
LAYER_SPEC = {
    "ln1/scale": (None, "emb"), "ln1/bias": (None, "emb"),
    "ln2/scale": (None, "emb"), "ln2/bias": (None, "emb"),
    "wq": ("emb", "q"), "bq": (None, "q"),
    "wk": ("emb", "k"), "bk": (None, "k"),
    "wv": ("emb", "v"), "bv": (None, "v"),
    "wo": ("v", "emb"), "bo": (None, "emb"),          # A^O = B_v^T (MHA)
    "mlp/w1": ("emb", "fc"), "mlp/b1": (None, "fc"),
    "mlp/w2": ("fc", "emb"), "mlp/b2": (None, "emb"),
}
TOP_SPEC = {
    "embed/tok": (None, "emb"), "embed/pos": (None, "emb"),
    "final_norm/scale": (None, "emb"), "final_norm/bias": (None, "emb"),
    "head": ("emb", None),
}


def width_dims(m: Dict) -> Dict[str, int]:
    d, H = m["d_model"], m["n_heads"]
    return {"emb": d, "q": d, "k": d, "v": d, "fc": m["d_ff"]}


def init_operator(key, src: Dict, dst: Dict, noise: float = 0.01) -> Dict:
    """The operator LiGO starts from: expanders [I; one-hot row copies] +
    noise (drawn per expander name, in sorted order), depth blends that
    stack (target layer k copies source layer k mod L1)."""
    d1, d2 = width_dims(src), width_dims(dst)
    keys = jax.random.split(key, len(d2) + 1)
    width = {}
    for i, name in enumerate(sorted(d2)):
        k1, k2 = jax.random.split(keys[i])
        a, b = d2[name], d1[name]
        eye = jnp.eye(a, b)
        if a > b:
            src_rows = jax.random.randint(k1, (a - b,), 0, b)
            eye = jnp.concatenate([jnp.eye(b), jax.nn.one_hot(src_rows, b)])
        width[name] = eye + noise * jax.random.normal(k2, (a, b))
    L1, L2 = src["n_layers"], dst["n_layers"]
    stack = jax.nn.one_hot(jnp.arange(L2) % L1, L1)
    depth = {"attn": {leaf: stack for leaf in LAYER_SPEC}}
    return {"width": width, "depth": depth}


def _get(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _put(tree, path, value):
    ks = path.split("/")
    for k in ks[:-1]:
        tree = tree.setdefault(k, {})
    tree[ks[-1]] = value


def grow(op: Dict, small: Dict, src: Dict, pr: Precision = REF, *,
         square: bool = False) -> Dict:
    """The grown tree, float32. ``square`` grows through the elementwise
    square of every expander and blend (the AdamW second-moment map)."""
    width = op["width"]

    def E(name):
        if name is None:
            return None
        e = width[name].astype(F32)
        return e * e if square else e

    def expand(W, e_in, e_out):
        W = W.astype(F32)
        if e_in is not None:
            W = pr.mm("ia,...ab->...ib", E(e_in), W)
        if e_out is not None:
            W = pr.mm("...ab,jb->...aj", W, E(e_out))
        return W

    def expand_vec(v, e_out):
        v = v.astype(F32)
        return v if e_out is None else pr.mm("ja,...a->...j", E(e_out), v)

    out: Dict = {}
    stack = small["layers"]["attn"]
    for path, (e_in, e_out) in LAYER_SPEC.items():
        W = _get(stack, path)
        wide = (expand_vec(W, e_out) if W.ndim == 2
                else expand(W, e_in, e_out))
        w = op["depth"]["attn"][path].astype(F32)
        if square:
            w = w * w
        _put(out, "layers/attn/" + path, pr.mm("kl,l...->k...", w, wide))
    for path, (e_in, e_out) in TOP_SPEC.items():
        if path == "head" and src.get("tie_embeddings", False):
            continue
        W = _get(small, path)
        _put(out, path, expand_vec(W, e_out) if W.ndim == 1
             else expand(W, e_in, e_out))
    return out


def ligo_loss(op, small, src: Dict, dst: Dict, batch,
              pr: Precision = REF):
    return mlm_loss(grow(op, small, src, pr), dst, batch, pr)


def sgd_momentum(op, mom, grads, *, lr: float, momentum: float):
    mom = jax.tree.map(lambda m, g: momentum * m + g, mom, grads)
    return jax.tree.map(lambda p, m: p - lr * m, op, mom), mom


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def warmup_cosine(step: float, *, base_lr: float, warmup_steps: int,
                  total_steps: int, end_frac: float) -> float:
    if step < warmup_steps:
        return base_lr * step / max(warmup_steps, 1)
    prog = min(max((step - warmup_steps)
                   / max(total_steps - warmup_steps, 1), 0.0), 1.0)
    return base_lr * (end_frac + (1 - end_frac) * 0.5
                      * (1 + math.cos(math.pi * prog)))


def clip_global(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                        jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, m, v, count: int, grads, *, lr: float, b1: float,
          b2: float, weight_decay: float, eps: float = 1e-8,
          store_dtype=None):
    """One AdamW update; decay on leaves of rank >= 2. The new parameters
    are rounded to ``store_dtype`` (the dtype the model keeps them in)."""
    count = count + 1
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count

    def upd(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        pf = p.astype(F32)
        if p.ndim >= 2:
            step = step + weight_decay * pf
        new = pf - lr * step
        if store_dtype is not None:
            new = new.astype(store_dtype)
        return new, m_, v_

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,             # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), count


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------
def worst_norm_gap(got: List[float], want: List[float],
                   keep: Optional[List[bool]] = None) -> float:
    """max over leaves of |got - want| / max(want, median of want): the gap
    between two norms, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    idx = [i for i in range(len(want)) if keep is None or keep[i]]
    med = sorted(want[i] for i in idx)[len(idx) // 2]
    return max(abs(got[i] - want[i]) / max(want[i], med, 1e-30)
               for i in idx)


def worst_rel_err(got, want) -> float:
    """max over leaves of ||got - want|| / ||want||."""
    out = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        w = w.astype(F32)
        d = jnp.linalg.norm((g.astype(F32) - w).ravel())
        out = max(out, float(d / jnp.maximum(jnp.linalg.norm(w.ravel()),
                                             1e-30)))
    return out
