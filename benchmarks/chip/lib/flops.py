"""Operations and bytes the algorithms need, computed from shapes.

The yardstick for every MFU and roofline share the benchmark reports. A
model is described by the ``model`` dict of a configuration file (layers,
width, heads, feed-forward width, vocabulary); nothing here reads the
program. Matrix multiplications count 2 operations per multiply-add;
recomputed work (rematerialisation) never counts.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def dims(m: Dict) -> Dict[str, int]:
    d, h = m["d_model"], m["n_heads"]
    dh = m.get("d_head", d // h)
    kv = m.get("n_kv_heads", h)
    return {"L": m["n_layers"], "d": d, "q": h * dh, "kv": kv * dh,
            "ff": m["d_ff"], "V": m["vocab_size"]}


def layer_matmul_params(m: Dict) -> int:
    """Weights of one layer that multiply activations (no norms, biases)."""
    x = dims(m)
    return (x["d"] * x["q"] + 2 * x["d"] * x["kv"] + x["q"] * x["d"]
            + 2 * x["d"] * x["ff"])


def matmul_params(m: Dict) -> int:
    """All layers' matrices plus the output head (tied or not); the
    embedding lookups do no multiplication."""
    x = dims(m)
    return x["L"] * layer_matmul_params(m) + x["d"] * x["V"]


def attn_context(seq: int, causal: bool) -> float:
    """Mean number of keys a query attends to over a sequence."""
    return (seq + 1) / 2 if causal else float(seq)


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward and backward of one token: 6 per matmul weight, plus the
    score and value products of attention (2 products x 2 ops x 3 passes)."""
    x = dims(m)
    ctx = attn_context(seq, m.get("causal", False))
    return 6.0 * matmul_params(m) + 12.0 * x["L"] * ctx * x["q"]


def prefill_flops(m: Dict, prompt_len: int) -> float:
    """Forward over a prompt of ``prompt_len`` true tokens, causal, with
    logits for the last position only."""
    x = dims(m)
    T = prompt_len
    attn = 4.0 * x["L"] * x["q"] * T * (T + 1) / 2
    return 2.0 * x["L"] * layer_matmul_params(m) * T + 2.0 * x["d"] * x["V"] \
        + attn


def decode_flops(m: Dict, lengths: Iterable[int]) -> float:
    """One decode round: one token for each active sequence, attending over
    its live length (the new token included)."""
    x = dims(m)
    per_tok = 2.0 * matmul_params(m)
    return sum(per_tok + 4.0 * x["L"] * x["q"] * n for n in lengths)


# ---------------------------------------------------------------------------
# LiGO growth (paper Eq. 8): per leaf, blend over depth and expand in width
# ---------------------------------------------------------------------------
def _expand(n: int, a: int, b: int, I: int, J: int) -> float:
    """Least operations for E_in W E_out^T over ``n`` (a, b) matrices into
    (I, J); an axis that is not grown has I == a (or J == b) and costs
    nothing."""
    in_first = ((2.0 * n * I * a * b if I != a else 0.0)
                + (2.0 * n * I * b * J if J != b else 0.0))
    out_first = ((2.0 * n * a * b * J if J != b else 0.0)
                 + (2.0 * n * I * a * J if I != a else 0.0))
    return min(in_first, out_first)


def _leaf_fwd(L1: int, L2: int, a: int, b: int, I: int, J: int) -> float:
    """Least operations for Omega[k] = sum_l w[k,l] (E_in W_l E_out^T) over
    one (L1, a, b) -> (L2, I, J) leaf stack: expand then blend, or blend in
    the small space then expand."""
    return min(_expand(L1, a, b, I, J) + 2.0 * L2 * L1 * I * J,
               2.0 * L2 * L1 * a * b + _expand(L2, a, b, I, J))


def ligo_leaves(src: Dict, dst: Dict) -> List[Tuple]:
    """(L1, L2, a, b, I, J) for every leaf of a dense pre-norm transformer
    with biases (BERT, GPT-2): layer stacks with depth blends, and the
    embedding, position table, final norm and head without."""
    s, t = dims(src), dims(dst)
    L1, L2 = s["L"], t["L"]
    d1, d2 = s["d"], t["d"]
    out = []
    for a, b, I, J in [(s["d"], s["q"], d2, t["q"]),            # wq
                       (s["d"], s["kv"], d2, t["kv"]),          # wk
                       (s["d"], s["kv"], d2, t["kv"]),          # wv
                       (s["q"], s["d"], t["q"], d2),            # wo
                       (s["d"], s["ff"], d2, t["ff"]),          # w1
                       (s["ff"], s["d"], t["ff"], d2)]:         # w2
        out.append((L1, L2, a, b, I, J))
    for b, J in [(d1, d2)] * 4 + [(s["q"], t["q"]), (s["kv"], t["kv"]),
                                  (s["kv"], t["kv"]), (d1, d2),
                                  (s["ff"], t["ff"]), (d1, d2)]:
        out.append((L1, L2, 1, b, 1, J))       # ln1, ln2 (scale, bias), biases
    V = s["V"]
    out += [(1, 1, V, d1, V, d2),                            # embed/tok
            (1, 1, src["max_seq"], d1, src["max_seq"], d2),  # embed/pos
            (1, 1, 1, d1, 1, d2), (1, 1, 1, d1, 1, d2)]      # final norm
    if not src.get("tie_embeddings", False):
        out.append((1, 1, d1, V, d2, V))                     # head
    return out


def ligo_apply_flops(src: Dict, dst: Dict) -> float:
    """One materialisation of the grown tree (parameters or one moment)."""
    total = 0.0
    for L1, L2, a, b, I, J in ligo_leaves(src, dst):
        if L1 == 1 and L2 == 1:                  # no depth blend
            total += _expand(1, a, b, I, J)
        else:
            total += _leaf_fwd(L1, L2, a, b, I, J)
    return total


def ligo_step_flops(src: Dict, dst: Dict, tokens: int, seq: int) -> float:
    """One LiGO step: the grown model's forward and backward over ``tokens``
    tokens, plus the growth's forward and its backward into the operator
    (counted as twice the forward, as for any contraction)."""
    return (train_flops_per_token(dst, seq) * tokens
            + 3.0 * ligo_apply_flops(src, dst))


def ligo_hop_flops(src: Dict, dst: Dict, steps: int, tokens: int,
                   seq: int) -> float:
    """A whole hop: ``steps`` LiGO steps, then the parameters and the two
    AdamW moments grown through the trained operator."""
    return (steps * ligo_step_flops(src, dst, tokens, seq)
            + 3.0 * ligo_apply_flops(src, dst))


# ---------------------------------------------------------------------------
# The fused blend-expand kernels, per launch, from the group's shapes
# ---------------------------------------------------------------------------
def blend_expand_fwd(G: int, L1: int, L2: int, E: int, I: int, A: int,
                     Bd: int, itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) of P[g,k,e] = B (sum_l w[g,k,l] W[g,l,e]) with
    w (G, L2, L1), B (I, A), W (G, L1, E, A, Bd) -> P (G, L2, E, I, Bd).
    Blend in the small space, then expand; every operand moves once."""
    n = G * E * L2
    ops = 2.0 * n * L1 * A * Bd + 2.0 * n * I * A * Bd
    byts = (G * L2 * L1 * 4 + I * A * 4
            + (G * L1 * E * A * Bd + G * L2 * E * I * Bd) * itemsize)
    return ops, float(byts)


def blend_expand_bwd(G: int, L1: int, L2: int, E: int, I: int, A: int,
                     Bd: int, itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) of the three cotangents of the fused forward:
    T_k = B^T dP_k, dW_l = sum_k w[k,l] T_k, dw[k,l] = <T_k, W_l>, and
    dB = sum_k dP_k (sum_l w[k,l] W_l)^T, the blend counted once."""
    n = G * E
    ops = (2.0 * n * L2 * A * I * Bd          # T_k
           + 2.0 * n * L1 * L2 * A * Bd       # dW
           + 2.0 * n * L2 * L1 * A * Bd       # dw
           + 2.0 * n * L2 * L1 * A * Bd       # the blend dB needs
           + 2.0 * n * L2 * I * A * Bd)       # dB
    byts = (G * L2 * L1 * 4 * 2 + I * A * 4 * 2
            + (2 * G * L1 * E * A * Bd + G * L2 * E * I * Bd) * itemsize)
    return ops, float(byts)


def roofline_seconds(ops: float, byts: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    c, m = ops / peak_flops, byts / peak_bw
    return (c, "compute") if c >= m else (m, "memory")

