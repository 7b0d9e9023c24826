"""Speculative decoding: draft with the pre-hop model, verify with the grown.

LiGO's premise is that the small pretrained model already encodes most of
the grown model's function — and during a live hop the engine literally
holds both param sets, so the small model is a *free* drafter. Each
scheduling round drafts K tokens per slot in ONE jitted launch of the small
decode program (a ``lax.scan`` over the same ``decode_step`` body the
vanilla path jits), then verifies all K in one batched launch of the grown
model over the K+1 inputs ``[last, s_1..s_K]``, producing the K+1
next-token distributions in a single pass.

Acceptance is decided host-side (the logits come back anyway — the vanilla
path already pays this transfer per token; the spec path pays it once per
K+1 tokens):

- **greedy**: accept the longest prefix where the draft matches the
  verifier argmax, then emit the verifier's own next token. Every emitted
  token is an argmax of the grown model's logits at the correct prefix, so
  the output is *bit-equal* to vanilla greedy decode (test-asserted) — the
  drafts only decide how many positions one launch advances.
- **sampled**: the standard reject-and-resample rule — accept draft ``s``
  with probability ``min(1, p_big(s)/p_small(s))``, else resample from
  ``normalize(max(p_big - p_small, 0))``. The draft program *returns* the
  exact adjusted distributions it sampled from, so the host-side rule uses
  the true ``p_small`` (no recomputation drift).

Rollback is positional, not copy-based: the verify launch writes cache
entries at ``pos..pos+K`` for every slot, and the engine then resets each
slot's position to its host-side truth (``true_len + len(tokens) - 1``).
Entries beyond a slot's position are masked by ``cur_len`` and overwritten
exactly when they next become valid — the same staleness contract the
continuous-batching cache already relies on. This is what makes a hop abort
mid-draft free: nothing to undo, positions never moved.

Randomness is a fixed per-slot PRNG chain: counter-based Philox keyed
``(seed, request uid, draw counter)`` host-side, so runs are reproducible
and slots are independent; the device-side draft sampler chains
``fold_in(seed, round, slot, step)`` keys the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import decode_step
from repro.obs import counter_group

# Program (re)builds per kind — lru_cache hits don't count, so a hop cycle
# that recompiles its draft/verify programs shows up here.
BUILD_COUNTS = counter_group("serve.spec.builds")

_TINY = 1e-20


# ---------------------------------------------------------------------------
# Sampling primitives (host + device twins)
# ---------------------------------------------------------------------------
def philox(seed: int, uid: int, counter: int) -> np.random.Generator:
    """Counter-based per-request RNG: a fresh generator per draw keyed by
    the draw index, so reproducibility never depends on call order."""
    bits = np.asarray([seed, uid, counter, 0], np.uint64)
    return np.random.Generator(np.random.Philox(counter=bits,
                                                key=[seed, uid]))


def adjust_probs(logits: np.ndarray, temperature: float,
                 top_p: float) -> np.ndarray:
    """Temperature + top-p adjusted distribution (float64, host-side).

    top-p keeps the smallest prefix of the descending-sorted distribution
    whose *preceding* cumulative mass is < top_p (top-1 always survives),
    then renormalises.
    """
    l = np.asarray(logits, np.float64)
    if temperature > 0:
        l = l / temperature
    l = l - l.max()
    p = np.exp(l)
    p /= p.sum()
    if top_p < 1.0:
        order = np.argsort(-p)
        ps = p[order]
        keep_sorted = np.concatenate([[True], np.cumsum(ps)[:-1] < top_p])
        keep = np.zeros_like(p, bool)
        keep[order] = keep_sorted
        p = np.where(keep, p, 0.0)
        p /= p.sum()
    return p


def device_adjust_probs(logits: jax.Array, temperature: float,
                        top_p: float) -> jax.Array:
    """The traced twin of :func:`adjust_probs` over (B, V) logits."""
    l = logits.astype(jnp.float32)
    if temperature > 0:
        l = l / temperature
    p = jax.nn.softmax(l, axis=-1)
    if top_p < 1.0:
        ps = jnp.sort(p, axis=-1)[:, ::-1]
        cum = jnp.cumsum(ps, axis=-1)
        prev = cum - ps                               # mass before each rank
        keep_sorted = prev < top_p                    # rank 0 always kept
        order = jnp.argsort(-p, axis=-1)
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(p.shape[0])[:, None], order].set(keep_sorted)
        p = jnp.where(keep, p, 0.0)
        p = p / p.sum(axis=-1, keepdims=True)
    return p


# ---------------------------------------------------------------------------
# Draft / verify programs (memoised per (cfg, K, ...))
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def make_draft_fn(cfg: ModelConfig, K: int, paged_kernel: bool = False):
    """Greedy drafter: one launch scans K+1 decode steps of the small
    model, feeding each argmax forward. Returns (tokens (B,K),
    logits (B,K,V), state).

    K+1 steps for K drafts, deliberately: step j caches its *input* token
    at pos+j, so stopping after K steps would leave position pos+K (the
    K-th draft's cache entry) unwritten — a hole the drafter would decode
    across on the next round whenever the verifier accepted everything.
    The extra step's output token is discarded; its cache write is the
    point. ``paged_kernel`` as in ``decode_step``."""
    BUILD_COUNTS.inc("draft")

    @jax.jit
    def draft(params, state, last):
        def body(carry, _):
            st, tok = carry
            logits, st2 = decode_step(params, cfg, st, {"tokens": tok},
                                      paged_kernel=paged_kernel)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (st2, nxt[:, None]), (nxt, logits)

        (st, _), (toks, logits) = jax.lax.scan(
            body, (state, last), None, length=K + 1)
        return (jnp.transpose(toks)[:, :K],
                jnp.transpose(logits, (1, 0, 2))[:, :K], st)

    return draft


@functools.lru_cache(maxsize=32)
def make_sampled_draft_fn(cfg: ModelConfig, K: int, temperature: float,
                          top_p: float, paged_kernel: bool = False):
    """Sampled drafter: same scan, but each step draws from the adjusted
    distribution with a per-(step, slot) key. Returns (tokens (B,K),
    probs (B,K,V) — the exact distributions sampled from — and state).

    Scans K+1 steps for K drafts for the same cache-completeness reason as
    :func:`make_draft_fn`; callers pass K+1 key rows (the last draw is
    discarded with its token)."""
    BUILD_COUNTS.inc("sampled_draft")

    @jax.jit
    def draft(params, state, last, keys):        # keys: (K+1, B, 2) uint32
        def body(carry, keys_k):
            st, tok = carry
            logits, st2 = decode_step(params, cfg, st, {"tokens": tok},
                                      paged_kernel=paged_kernel)
            probs = device_adjust_probs(logits, temperature, top_p)
            nxt = jax.vmap(
                lambda kk, pp: jax.random.categorical(
                    kk, jnp.log(jnp.maximum(pp, _TINY))))(
                        keys_k, probs).astype(jnp.int32)
            return (st2, nxt[:, None]), (nxt, probs)

        (st, _), (toks, probs) = jax.lax.scan(body, (state, last), keys)
        return (jnp.transpose(toks)[:, :K],
                jnp.transpose(probs, (1, 0, 2))[:, :K], st)

    return draft


def draft_keys(seed: int, round_idx: int, K: int, slots: int) -> jax.Array:
    """The device drafter's key chain: fold (round, step, slot) into a fixed
    base so every draw has a stable identity across runs."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
    keys = jax.random.split(base, K * slots)
    return keys.reshape(K, slots, 2)


@functools.lru_cache(maxsize=32)
def make_verify_fn(cfg: ModelConfig, K1: int, want_hidden: bool,
                   paged_kernel: bool = False):
    """Verifier: one launch scans the grown model's decode body over the
    K+1 given inputs (no feedback — the tokens are fixed), yielding all
    K+1 next-token logits. The body is the same ``decode_step`` the vanilla
    path runs, which is what makes greedy acceptance bit-equal.

    Returns (logits (B,K1,V)[, prenorm hidden (B,K1,D)], state).
    """
    BUILD_COUNTS.inc("verify")

    @jax.jit
    def verify(params, state, inputs):                # inputs: (B, K1)
        def body(st, tok_col):                        # tok_col: (B,)
            out = decode_step(params, cfg, st, {"tokens": tok_col[:, None]},
                              return_prenorm=want_hidden,
                              paged_kernel=paged_kernel)
            if want_hidden:
                return out[1], (out[0], out[2][:, 0])
            return out[1], (out[0],)

        st, ys = jax.lax.scan(body, state, jnp.transpose(inputs))
        logits = jnp.transpose(ys[0], (1, 0, 2))
        if want_hidden:
            return logits, jnp.transpose(ys[1], (1, 0, 2)), st
        return logits, st

    return verify


# ---------------------------------------------------------------------------
# Host-side acceptance
# ---------------------------------------------------------------------------
def accept_greedy(draft_toks: np.ndarray, verify_logits: np.ndarray):
    """Longest-prefix-match acceptance for one slot.

    draft_toks: (K,); verify_logits: (K+1, V). Returns (emit, accepted):
    the tokens to emit (accepted drafts + the verifier's own next token)
    and the accepted-draft count.
    """
    g = np.argmax(verify_logits, axis=-1)
    K = draft_toks.shape[0]
    a = 0
    while a < K and int(draft_toks[a]) == int(g[a]):
        a += 1
    return [int(t) for t in draft_toks[:a]] + [int(g[a])], a


def accept_sampled(draft_toks: np.ndarray, draft_probs: np.ndarray,
                   verify_logits: np.ndarray, *, temperature: float,
                   top_p: float, seed: int, uid: int, counter: int):
    """Reject-and-resample acceptance for one slot.

    draft_toks: (K,); draft_probs: (K, V) — the device drafter's exact
    distributions; verify_logits: (K+1, V). Returns (emit, accepted,
    draws_used).
    """
    K = draft_toks.shape[0]
    emit, a, draws = [], 0, 0
    for j in range(K):
        s = int(draft_toks[j])
        pb = adjust_probs(verify_logits[j], temperature, top_p)
        ps = np.asarray(draft_probs[j], np.float64)
        u = philox(seed, uid, counter + draws).random()
        draws += 1
        if u < min(1.0, pb[s] / max(ps[s], _TINY)):
            emit.append(s)
            a += 1
            continue
        resid = np.maximum(pb - ps, 0.0)
        tot = resid.sum()
        resid = resid / tot if tot > 0 else pb
        emit.append(int(philox(seed, uid, counter + draws).choice(
            len(resid), p=resid)))
        draws += 1
        return emit, a, draws
    pb = adjust_probs(verify_logits[K], temperature, top_p)
    emit.append(int(philox(seed, uid, counter + draws).choice(
        len(pb), p=pb)))
    draws += 1
    return emit, a, draws
