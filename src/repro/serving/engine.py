"""Continuous-batching serving engine with a hot-swappable model.

One fixed block of ``slots`` batch rows shares a single decode program;
every row carries its own position (``state["pos"]``: (slots,) int32), so
sessions prefill into free rows and decode in lock-step regardless of where
each one is in its sequence. Scheduling per step: admit waiting requests
into free slots (one prefill each), then advance every live slot — one
token via the vanilla decode program, or up to ``spec_k + 1`` tokens via a
draft/verify speculative round when a drafter is resident (the pre-hop
model, installed by the hop controller after a successful swap).

**KV layout.** The default is *paged*: slots share a pool of fixed-size
blocks through per-slot page tables (``serving.kv_pages``), so a slot pays
for the pages its sequence actually covers instead of a dense ``max_len``
row. The dense layout survives behind ``kv_layout="dense"`` as the
correctness oracle (and for windowed/recurrent families, which the paged
path does not cover). The engine owns positions host-side
(``self.pos_host``) and re-asserts them into the device state before every
launch — that single convention is also what makes speculative rollback
free: a rejected draft just means the position does not advance over it.

The engine's serving buffers — ``(cfg, params, state)`` plus the jitted
prefill/decode/insert programs — are swapped as a unit by
:meth:`install`, which the hop controller (``repro.serving.hotswap``) calls
between two decode steps. Nothing in the engine is mutated until the swap,
so a hop aborted at any stage (including mid-draft) leaves it decoding the
old weights untouched.
"""
from __future__ import annotations

import functools
import time
import warnings
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models.model import (_pad_attn_caches, decode_step, forward,
                                init_decode_state, unembed)
from repro.serving import speculative as spec
from repro.serving.admission import AdmissionQueue, Request
from repro.serving.kv_pages import (PageAllocator, init_paged_caches,
                                    paged_supported, scatter_row_blocks)

_EMA = 0.3          # telemetry smoothing for acceptance / launch costs
_RECENT_STEPS = 4096  # exact-window size behind the step_times_ms shim


class _CachesDonated:
    """Call a program that donates its state's caches with the state whole.

    ``jax.jit`` donates whole arguments, and only the caches may go: the
    page table is the allocator's cached device copy and the positions may
    be shared with other holders (a migrated state keeps its source's). So
    the program takes ``(…, caches, rest, …)`` and this splits the state
    argument at position ``at`` into those two."""

    def __init__(self, jitted, at: int):
        self.jitted, self.at = jitted, at

    def _split(self, args):
        state = args[self.at]
        rest = {k: v for k, v in state.items() if k != "caches"}
        return args[:self.at] + (state["caches"], rest) + args[self.at + 1:]

    def __call__(self, *args):
        return self.jitted(*self._split(args))

    def lower(self, *args):
        return self.jitted.lower(*self._split(args))


@functools.lru_cache(maxsize=16)
def make_serving_fns(cfg: ModelConfig, cap: int, layout: str = "dense",
                     want_hidden: bool = False, paged_kernel: bool = False):
    """(prefill_one, decode_many, insert) jitted for one architecture.

    Memoised on ``(cfg, cap, layout, want_hidden, paged_kernel)`` (configs
    are frozen dataclasses): a hop back to an architecture the process has
    already served — or a second engine on the same config — reuses the
    compiled programs instead of re-tracing, so ``install`` costs reference
    flips, not compiles.

    ``cap`` is the cache row capacity: the (window-clamped) ``max_len`` for
    the dense layout, the page-aligned ``padded_len`` for the paged one.
    With ``layout="paged"`` the state carries ``{"caches": pools, "pos",
    "pages"}`` and ``insert`` scatters the prefilled row into the slot's
    pages; decode reads through the table — in place with the
    paged-attention kernel when ``paged_kernel``
    (:meth:`ServingEngine.paged_kernel`), else by gathering each layer's
    pool. ``want_hidden`` additionally returns the
    pre-final-norm residual stream (prefill: (Tp, D); decode: (B, D)) — the
    engine preserves it per slot so a depth-only hop can replay just the new
    layers (``core.grow_cache``).

    Both programs end with the greedy pick, ``argmax`` of their logits as
    int32 (prefill: a scalar; decode: (B,)): ``(logits, caches|state,
    [hidden,] picks)``. Under greedy decoding the engine copies only the
    picks to the host and leaves the logits on the device.

    ``decode_many`` and ``insert`` donate the state's caches: the state
    passed in is spent, and the caller keeps the one returned.

    ``prefill_one`` takes a right-padded (1, Tp) prompt plus its true
    length; padding positions write garbage cache entries *beyond* the
    session's position, and decode overwrites each one exactly when it
    becomes valid (slot ``cur_len-1``), so they are never attended to.
    """
    assert layout in ("dense", "paged"), layout

    @jax.jit
    def prefill_one(params, tokens, true_len):
        out = forward(params, cfg, {"tokens": tokens}, mode="prefill",
                      return_prenorm=want_hidden)
        hidden, caches = out[0], out[1]
        caches = _pad_attn_caches(caches, cfg, cap)
        logits = unembed(params, cfg,
                         jnp.take(hidden[0], true_len - 1, axis=0))
        picks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if want_hidden:
            return logits, caches, out[3][0], picks
        return logits, caches, picks

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_many(params, caches, rest, tokens):
        out = decode_step(params, cfg, {**rest, "caches": caches},
                          {"tokens": tokens}, return_prenorm=want_hidden,
                          paged_kernel=paged_kernel)
        picks = jnp.argmax(out[0], axis=-1).astype(jnp.int32)
        if want_hidden:
            return out[0], out[1], out[2][:, 0], picks
        return out[0], out[1], picks

    if layout == "dense":
        @functools.partial(jax.jit, donate_argnums=(0,))
        def insert(caches, rest, caches1, pos1, slot):
            # every cache leaf (attn K/V, ssm conv/state) carries batch at
            # axis 1
            ins = lambda c, c1: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731,E501
                c, c1, slot, axis=1)
            return {**rest, "caches": jax.tree.map(ins, caches, caches1),
                    "pos": rest["pos"].at[slot].set(pos1)}
    else:
        @functools.partial(jax.jit, donate_argnums=(0,))
        def insert(caches, rest, caches1, pos1, slot):
            pages_row = rest["pages"][slot]           # (P,)
            sc = lambda pool, c1: scatter_row_blocks(  # noqa: E731
                pool, pages_row, c1[:, 0])
            return {"caches": jax.tree.map(sc, caches, caches1),
                    "pos": rest["pos"].at[slot].set(pos1),
                    "pages": rest["pages"]}

    return (prefill_one, _CachesDonated(decode_many, 1),
            _CachesDonated(insert, 0))


class ServingEngine:
    """Continuous batching over ``slots`` sessions with admission control.

    ``prompt_budget`` bounds admissible prompt length (longer → rejected at
    the door); ``max_len = prompt_budget + gen_budget`` is each slot's cache
    budget, and a request's ``max_new`` is clamped so it can never outrun
    its slot.

    Fast-path knobs: ``kv_layout``/``block_size``/``pool_blocks`` control
    the paged cache (``pool_blocks=None`` sizes the pool so admission never
    blocks; smaller pools create real backpressure — admission reserves a
    request's worst case up front, so admitted requests always finish);
    ``temperature``/``top_p``/``seed`` select sampling on the (verifier's)
    logits with a reproducible per-slot Philox chain, greedy by default;
    ``spec_k`` arms speculative decoding — drafting actually starts when a
    hop installs the pre-hop model via :meth:`adopt_drafter`, and
    auto-disables if the measured speedup estimate drops below 1.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 prompt_budget: int = 64, gen_budget: int = 32,
                 queue_capacity: int = 64, mesh=None,
                 kv_layout: str = "paged", block_size: int = 16,
                 pool_blocks: Optional[int] = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 seed: int = 0, spec_k: int = 0,
                 spec_autodisable: bool = True,
                 keep_residual: Optional[bool] = None):
        assert kv_layout in ("paged", "dense"), kv_layout
        self.slots = slots
        self.prompt_budget = prompt_budget
        self.max_len = prompt_budget + gen_budget
        self.mesh = mesh
        self.queue = AdmissionQueue(queue_capacity)
        self.requests: List[Request] = []
        self.slot_req: List[Optional[Request]] = [None] * slots
        # decode-step walls: bounded recent window (exact percentiles for
        # the report) + an obs histogram (full-run p50/p99 in O(buckets)
        # memory). The old unbounded ``step_times_ms`` list is a
        # deprecated property shim over the window.
        self._recent_steps: deque = deque(maxlen=_RECENT_STEPS)
        self._h_step = obs.histogram("serve.decode.step_ms")
        self._h_queue_wait = obs.histogram("serve.request.queue_wait_ms")
        self._h_ttft = obs.histogram("serve.request.ttft_ms")
        self._h_tok_s = obs.histogram("serve.request.tokens_per_s",
                                      buckets=obs.RATE_BUCKETS)
        self._h_pages = obs.histogram("serve.kv.pages_read",
                                      buckets=obs.LOG10_BUCKETS)
        self._h_draft = obs.histogram("serve.spec.draft_ms")
        self._h_verify = obs.histogram("serve.spec.verify_ms")
        self._g_acc = obs.gauge("serve.spec.acc_ema")
        self._g_est = obs.gauge("serve.spec.est_speedup")
        self._c_req = obs.counter_group("serve.requests")
        for k in ("submitted", "done", "rejected", "dropped", "deferred"):
            self._c_req.inc(k, 0)       # declare: dump shows explicit zeros
        # served tokens by where their choice was made: the program's greedy
        # pick ("device") or a draw from a logits row on the host ("host")
        self._c_picks = obs.counter_group("serve.picks")
        for k in ("device", "host"):
            self._c_picks.inc(k, 0)
        self.decode_steps = 0
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.spec_k = int(spec_k)
        # the auto-disable heuristic reads wall-clock costs, so scheduling
        # becomes timing-dependent; deterministic runs can turn it off
        self.spec_autodisable = bool(spec_autodisable)
        self.kv_layout_requested = kv_layout
        self.kv_fallback = False
        if kv_layout == "paged" and not paged_supported(cfg):
            # windowed/recurrent: dense ring cache. Fall back loudly — a
            # silent switch made the serve report lie about the layout.
            kv_layout = "dense"
            self.kv_fallback = True
            warnings.warn(
                f"{cfg.name}: paged KV layout unsupported "
                f"(family={cfg.family!r}, window={cfg.window}); serving "
                "with the dense ring cache instead", stacklevel=2)
        self.kv_layout = kv_layout
        self.alloc: Optional[PageAllocator] = None
        if kv_layout == "paged":
            self.alloc = PageAllocator(slots, self.max_len, block_size,
                                       pool_blocks)
        if keep_residual is None:
            keep_residual = paged_supported(cfg)
        self.keep_residual = bool(keep_residual) and paged_supported(cfg)
        self.pos_host = np.zeros((slots,), np.int64)
        self.resid: Optional[np.ndarray] = None
        self.resid_from = np.zeros((slots,), np.int64)
        # drafter (speculative decoding) — installed by adopt_drafter
        self.d_cfg: Optional[ModelConfig] = None
        self.d_params = None
        self.d_state = None
        self.spec_enabled = False
        self.spec_stats: Dict[str, Any] = {}
        self.install(cfg, params, None)

    # -- serving buffers ----------------------------------------------------
    def _cap_for(self, cfg: ModelConfig) -> int:
        if self.kv_layout == "paged":
            return self.alloc.padded_len
        return min(cfg.window, self.max_len) if cfg.window else self.max_len

    def paged_kernel(self, cfg: ModelConfig) -> bool:
        """Does ``cfg``'s decode round read the pools in place with the
        paged-attention kernel (``kernels.ops.paged_kernel_ok``: on one TPU,
        pages of whole tiles)? Otherwise it gathers through the table."""
        return self.alloc is not None and kernel_ops.paged_kernel_ok(
            self.alloc.block_size, cfg.n_kv_heads * cfg.d_head, self.mesh)

    def fresh_state(self, cfg: ModelConfig):
        if self.kv_layout == "paged":
            return {"caches": init_paged_caches(cfg, self.alloc.n_blocks,
                                                self.alloc.block_size),
                    "pos": jnp.zeros((self.slots,), jnp.int32),
                    "pages": self.alloc.device_table()}
        st = init_decode_state(cfg, self.slots, self.max_len)
        return {"caches": st["caches"],
                "pos": jnp.zeros((self.slots,), jnp.int32)}

    def install(self, cfg: ModelConfig, params, state) -> None:
        """Swap the serving buffers (the final act of a hop). The new jit
        handles are created first, so the visible mutation is just reference
        assignment between two decode steps."""
        if self.kv_layout == "paged":
            assert paged_supported(cfg), \
                f"{cfg.name}: paged KV unsupported; use kv_layout='dense'"
        cap = self._cap_for(cfg)
        fns = make_serving_fns(cfg, cap, self.kv_layout, self.keep_residual,
                               self.paged_kernel(cfg))
        if state is None:
            state = self.fresh_state(cfg)
        if obs.active_ledger() is not None:
            # compile-time cost pass (never inside jit): read the decode
            # step's measured FLOPs back from the compiled program and
            # reconcile against the 2N-per-token model. AOT-lowered here so
            # the ledger-off path pays nothing.
            from repro.obs import costs
            costs.measure_jitted(
                f"decode_step[{cfg.name}]", fns[1], params, state,
                jax.ShapeDtypeStruct((self.slots, 1), jnp.int32),
                modelled_flops=2.0 * cfg.active_param_count() * self.slots,
                n_devices=1 if self.mesh is None else self.mesh.size,
                per_call_units=self.slots)
        hopped = hasattr(self, "cfg")
        if hopped:
            obs.event("serve.install", src=self.cfg.name, dst=cfg.name,
                      live=len(self.live))
        self.cfg, self.params, self.state = cfg, params, state
        self.cap = cap
        self._prefill, self._decode, self._insert = fns
        if self.keep_residual:
            if (self.resid is None
                    or self.resid.shape != (self.slots, cap, cfg.d_model)):
                self.resid = np.zeros((self.slots, cap, cfg.d_model),
                                      np.float32)
                self.resid_from[:] = self.pos_host
            elif hopped:
                # pre-hop residuals describe the old model's function
                self.resid_from[:] = self.pos_host

    # -- speculative drafter -------------------------------------------------
    def adopt_drafter(self, cfg1: ModelConfig, params1, state1) -> bool:
        """Keep the pre-hop model resident as a speculative drafter. Its
        decode state is the live pre-hop state — caches already hold every
        slot's history, so drafting starts immediately, and with a lossless
        (LEMON) hop the first round's acceptance is 100% by construction.
        """
        if self.spec_k <= 0 or cfg1.window or self.cfg.window:
            return False
        if cfg1.vocab_size != self.cfg.vocab_size:
            return False
        if self.kv_layout == "paged" and not paged_supported(cfg1):
            return False
        self.d_cfg, self.d_params, self.d_state = cfg1, params1, state1
        cap = self._cap_for(cfg1)
        if cap != self.cap:
            self.d_cfg = self.d_params = self.d_state = None
            return False
        self._d_prefill, _, self._d_insert = make_serving_fns(
            cfg1, cap, self.kv_layout, False, self.paged_kernel(cfg1))
        if self.temperature > 0:
            self._draft = spec.make_sampled_draft_fn(
                cfg1, self.spec_k, self.temperature, self.top_p,
                self.paged_kernel(cfg1))
        else:
            self._draft = spec.make_draft_fn(cfg1, self.spec_k,
                                             self.paged_kernel(cfg1))
        self._verify = spec.make_verify_fn(self.cfg, self.spec_k + 1,
                                           self.keep_residual,
                                           self.paged_kernel(self.cfg))
        self.spec_enabled = True
        self.spec_stats = {"rounds": 0, "accepted": 0, "drafted": 0,
                           "acc_ema": None, "first_round_acc": None,
                           "c_draft": None, "c_verify": None,
                           "est_speedup": None, "drafter": cfg1.name,
                           "disabled": None}
        return True

    def drop_drafter(self, reason: str = "dropped") -> None:
        self.d_cfg = self.d_params = self.d_state = None
        if self.spec_enabled:
            self.spec_stats["disabled"] = reason
        self.spec_enabled = False

    # -- request lifecycle --------------------------------------------------
    def submit(self, prompt, max_new: int) -> Request:
        req = Request(prompt=list(prompt), max_new=max_new)
        req.sample_key = len(self.requests)
        req.t_submit = time.perf_counter()
        self.requests.append(req)
        self._c_req.inc("submitted")
        if not (0 < len(req.prompt) <= self.prompt_budget):
            req.status = "rejected"
            self.queue.rejected += 1
            self._c_req.inc("rejected")
            return req
        req.max_new = min(max_new, self.max_len - len(req.prompt))
        self.queue.submit(req)
        return req

    @property
    def live(self) -> List[Request]:
        return [r for r in self.slot_req if r is not None]

    def counts(self) -> Dict[str, int]:
        c = {"done": 0, "running": 0, "queued": 0, "rejected": 0,
             "dropped": 0}
        for r in self.requests:
            c[r.status] = c.get(r.status, 0) + 1
        return c

    def has_work(self) -> bool:
        return bool(len(self.queue)) or any(
            r is not None for r in self.slot_req)

    # -- decode-step timing ---------------------------------------------------
    def _observe_step(self, ms: float) -> None:
        self._recent_steps.append(ms)
        self._h_step.observe(ms)

    @property
    def step_times_ms(self) -> List[float]:
        """Deprecated: the old unbounded per-step list, now a bounded
        recent window (last ``_RECENT_STEPS`` steps). Use
        :meth:`decode_step_percentiles` or the ``serve.decode.step_ms``
        obs histogram instead."""
        warnings.warn(
            "ServingEngine.step_times_ms is deprecated; use "
            "decode_step_percentiles() or the 'serve.decode.step_ms' "
            "histogram in repro.obs.REGISTRY",
            DeprecationWarning, stacklevel=2)
        return list(self._recent_steps)

    def decode_step_percentiles(self, *qs: float) -> Tuple[float, ...]:
        """Exact percentiles over the recent decode-step window (ms)."""
        if not self._recent_steps:
            return tuple(float("nan") for _ in qs)
        arr = np.asarray(self._recent_steps)
        return tuple(float(np.percentile(arr, q)) for q in qs)

    # -- picking tokens -------------------------------------------------------
    def _rows(self, out) -> np.ndarray:
        """What the host reads of a prefill or decode program's output
        ``out`` to pick tokens, one row per sequence: under greedy decoding
        the program's picks, each a length-1 row; else the logits rows.
        The rest stays on the device."""
        if self.temperature <= 0:
            return np.asarray(out[-1]).reshape(-1, 1)
        logits = np.asarray(out[0])
        return logits.reshape(-1, logits.shape[-1])

    def _pick_token(self, req: Request, row: np.ndarray) -> int:
        """The next token of ``req`` from its ``row`` of :meth:`_rows`.

        Under greedy decoding ``row`` is the program's choice, a length-1
        int array, and is served as it is: the (slots, V) logits never
        leave the device. With ``temperature > 0`` it is the logits row,
        and ``req``'s Philox chain draws from it on the host."""
        if self.temperature <= 0:
            self._c_picks.inc("device")
            return int(row[0])
        self._c_picks.inc("host")
        p = spec.adjust_probs(row, self.temperature, self.top_p)
        rng = spec.philox(self.seed, req.sample_key, req.n_draws)
        req.n_draws += 1
        return int(rng.choice(len(p), p=p))

    def _append_tokens(self, req: Request, toks) -> int:
        """Append until the request's budget stops it; returns #appended."""
        n = 0
        for t in toks:
            req.tokens.append(int(t))
            n += 1
            if (len(req.tokens) >= req.max_new
                    or req.true_len + len(req.tokens) >= self.max_len):
                break
        return n

    # -- scheduling ---------------------------------------------------------
    def _sync_state(self, state):
        """Re-assert host truth into a device state before a launch: the
        per-slot positions (speculative rollback is exactly this) and the
        current page table."""
        out = {**state, "pos": jnp.asarray(self.pos_host, jnp.int32)}
        if self.alloc is not None:
            out["pages"] = self.alloc.device_table()
        return out

    def _worst_len(self, req: Request) -> int:
        """Worst-case backed length: prompt + full budget + the farthest a
        speculative verify can write ahead of the final position."""
        return min(len(req.prompt) + req.max_new + max(self.spec_k, 0),
                   self.cap)

    def _admit(self) -> None:
        for slot in range(self.slots):
            if self.slot_req[slot] is not None:
                continue
            if self.alloc is not None:
                head = self.queue.peek()
                if head is None:
                    return
                if not self.alloc.can_admit(self._worst_len(head)):
                    self._c_req.inc("deferred")
                    return              # stays queued: deferred, never dropped
            req = self.queue.pop()
            if req is None:
                return
            wait_ms = (time.perf_counter() - req.t_submit) * 1e3
            self._h_queue_wait.observe(wait_ms)
            req.true_len = len(req.prompt)
            if self.alloc is not None:
                self.alloc.admit(slot, req.true_len, self._worst_len(req))
            toks = np.zeros((1, self.prompt_budget), np.int32)
            toks[0, :req.true_len] = req.prompt
            # admission to the first token picked on the host
            with obs.span("serve.prefill", uid=req.uid, slot=slot,
                          prompt_len=req.true_len,
                          queue_wait_ms=round(wait_ms, 3)):
                out = self._prefill(self.params, jnp.asarray(toks),
                                    jnp.asarray(req.true_len))
                self.state = self._insert(
                    self._sync_state(self.state), out[1],
                    jnp.asarray(req.true_len, jnp.int32),
                    jnp.asarray(slot, jnp.int32))
                self.pos_host[slot] = req.true_len
                if self.keep_residual:
                    h = np.asarray(out[2], np.float32)
                    self.resid[slot, :req.true_len] = h[:req.true_len]
                    self.resid_from[slot] = 0
                if self.d_cfg is not None:
                    d_out = self._d_prefill(self.d_params, jnp.asarray(toks),
                                            jnp.asarray(req.true_len))
                    self.d_state = self._d_insert(
                        self._sync_state(self.d_state), d_out[1],
                        jnp.asarray(req.true_len, jnp.int32),
                        jnp.asarray(slot, jnp.int32))
                req.tokens.append(self._pick_token(req, self._rows(out)[0]))
                req.t_first = time.perf_counter()
            self._h_ttft.observe((req.t_first - req.t_submit) * 1e3)
            req.status, req.slot = "running", slot
            self.slot_req[slot] = req
            self._finish_if_done(req)

    def _finish_if_done(self, req: Request) -> None:
        if (len(req.tokens) >= req.max_new
                or req.true_len + len(req.tokens) >= self.max_len):
            req.status = "done"
            req.t_done = time.perf_counter()
            self._c_req.inc("done")
            dt = req.t_done - req.t_submit
            if dt > 0:
                self._h_tok_s.observe(len(req.tokens) / dt)
            self.slot_req[req.slot] = None
            if self.alloc is not None:
                self.alloc.release(req.slot)
            self.pos_host[req.slot] = 0
        else:
            self.pos_host[req.slot] = req.true_len + len(req.tokens) - 1

    def _spec_ready(self, active) -> bool:
        if not (self.spec_enabled and self.d_cfg is not None
                and self.spec_k > 0):
            return False
        K = self.spec_k
        return all(self.pos_host[i] + K + 1 <= self.cap for i, _ in active)

    def step(self) -> bool:
        """One scheduling iteration. Returns True while work remains."""
        self._admit()
        active = [(i, r) for i, r in enumerate(self.slot_req)
                  if r is not None]
        if active:
            if self._spec_ready(active):
                self._spec_round(active)
            else:
                self._plain_round(active)
        return self.has_work()

    def _round_attrs(self, active, ahead: int) -> Dict[str, int]:
        """The ``serve.decode`` span's attributes: the live slots and, paged,
        the pages the round touches — each slot's pages up to the farthest
        position it writes (``ahead`` past its own), which is what the
        decode program reads; also observed as ``serve.kv.pages_read``."""
        attrs = {"active": len(active)}
        if self.alloc is not None:
            attrs["pages"] = sum(
                self.alloc.pages_for(int(self.pos_host[i]) + ahead)
                for i, _ in active)
            self._h_pages.observe(attrs["pages"])
        return attrs

    def _plain_round(self, active) -> None:
        if self.alloc is not None:
            for i, _ in active:
                self.alloc.ensure(i, int(self.pos_host[i]) + 1)
        last = np.zeros((self.slots, 1), np.int32)
        for i, r in active:
            last[i, 0] = r.tokens[-1]
        state = self._sync_state(self.state)
        with obs.span("serve.decode", **self._round_attrs(active, 1)) as sp:
            out = self._decode(self.params, state, jnp.asarray(last))
            out[-1].block_until_ready()
        self._observe_step(sp.dur_ms)
        self.decode_steps += 1
        self.state = out[1]
        with obs.span("serve.sample", active=len(active),
                      picked="device" if self.temperature <= 0 else "host"):
            rows = self._rows(out)
            if self.keep_residual:
                h = np.asarray(out[2], np.float32)
            for i, r in active:
                if self.keep_residual:
                    self.resid[i, self.pos_host[i]] = h[i]
                r.tokens.append(self._pick_token(r, rows[i]))
                self._finish_if_done(r)

    def _spec_round(self, active) -> None:
        K = self.spec_k
        if self.alloc is not None:
            for i, _ in active:
                self.alloc.ensure(i, int(self.pos_host[i]) + K + 1)
        last = np.zeros((self.slots, 1), np.int32)
        for i, r in active:
            last[i, 0] = r.tokens[-1]
        d_state = self._sync_state(self.d_state)
        state = self._sync_state(self.state)
        with obs.span("serve.decode", spec=K,
                      **self._round_attrs(active, K + 1)) as sp:
            t0 = time.perf_counter()
            if self.temperature > 0:
                keys = spec.draft_keys(self.seed, self.spec_stats["rounds"],
                                       K + 1, self.slots)
                toks, probs, d_state2 = self._draft(self.d_params, d_state,
                                                    jnp.asarray(last), keys)
            else:
                toks, probs, d_state2 = self._draft(self.d_params, d_state,
                                                    jnp.asarray(last))
            toks.block_until_ready()
            t1 = time.perf_counter()
            draft_toks = np.asarray(toks)
            inputs = np.concatenate([last, draft_toks.astype(np.int32)],
                                    axis=1)
            v_out = self._verify(self.params, state, jnp.asarray(inputs))
            v_out[0].block_until_ready()
            t2 = time.perf_counter()
        self._observe_step(sp.dur_ms)
        self.decode_steps += 1
        with obs.span("serve.sample", active=len(active), spec=K):
            acc_total = self._accept(active, v_out, draft_toks, probs,
                                     d_state2)
        self._spec_telemetry(len(active), acc_total, t1 - t0, t2 - t1)

    def _accept(self, active, v_out, draft_toks, probs, d_state2) -> int:
        """Take each slot's accepted draft tokens and the verifier's own;
        returns how many draft tokens were accepted in all."""
        K = self.spec_k
        L = np.asarray(v_out[0])                       # (slots, K+1, V)
        hid = (np.asarray(v_out[1], np.float32)
               if self.keep_residual else None)
        self.d_state = d_state2
        self.state = v_out[-1]
        draft_probs = np.asarray(probs) if self.temperature > 0 else None
        acc_total = 0
        for i, r in active:
            if self.temperature > 0:
                emit, a, draws = spec.accept_sampled(
                    draft_toks[i], draft_probs[i], L[i],
                    temperature=self.temperature, top_p=self.top_p,
                    seed=self.seed, uid=r.sample_key, counter=r.n_draws)
                r.n_draws += draws
            else:
                emit, a = spec.accept_greedy(draft_toks[i], L[i])
            acc_total += a
            r.acc_ema = (a / K if r.acc_ema is None
                         else _EMA * (a / K) + (1 - _EMA) * r.acc_ema)
            if hid is not None:
                p0 = int(self.pos_host[i])
                self.resid[i, p0:p0 + K + 1] = hid[i]
            self._append_tokens(r, emit)
            self._finish_if_done(r)
        return acc_total

    def _spec_telemetry(self, n_active: int, acc_total: int,
                        t_draft: float, t_verify: float) -> None:
        st = self.spec_stats
        K = self.spec_k
        mean_a = acc_total / max(1, n_active)
        if st["rounds"] == 0:
            st["first_round_acc"] = mean_a / K
        st["rounds"] += 1
        st["accepted"] += acc_total
        st["drafted"] += n_active * K
        ema = lambda old, new: (new if old is None                  # noqa: E731
                                else _EMA * new + (1 - _EMA) * old)
        st["acc_ema"] = ema(st["acc_ema"], mean_a / K)
        st["c_draft"] = ema(st["c_draft"], t_draft / K)   # per drafted token
        st["c_verify"] = ema(st["c_verify"], t_verify)    # per launch
        est = ((st["acc_ema"] * K + 1)
               / (1 + K * st["c_draft"] / max(st["c_verify"], 1e-9)))
        st["est_speedup"] = est
        self._h_draft.observe(t_draft * 1e3)
        self._h_verify.observe(t_verify * 1e3)
        self._g_acc.set(st["acc_ema"])
        self._g_est.set(est)
        if self.spec_autodisable and st["rounds"] >= 3 and est < 1.0:
            self.spec_enabled = False
            st["disabled"] = (f"est speedup {est:.2f}x < 1 after "
                              f"{st['rounds']} rounds")
            print(f"[spec] drafting auto-disabled: {st['disabled']}")

    def run(self, *, on_step=None, max_steps: int = 100_000) -> None:
        """Drain the queue; ``on_step(engine)`` runs between decode steps —
        the hop controller's ``poll`` hooks in here."""
        for _ in range(max_steps):
            more = self.step()
            if on_step is not None:
                on_step(self)
            if not more:
                return
        raise RuntimeError(f"engine did not drain in {max_steps} steps")

    # -- cache migration fallback -------------------------------------------
    def reprefill_state(self, params, cfg: ModelConfig):
        """The universal cache-migration fallback: rebuild every live
        session's decode state by re-running prefill over its token history
        under ``params``/``cfg``. Exact by construction (it *is* the grown
        model's own prefill), at the cost of one prompt-length forward per
        live session."""
        state = self.fresh_state(cfg)
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            # cache holds prompt + all generated tokens except the newest
            # (decode writes its *input* token); same layout re-derived here
            hist = (list(req.prompt) + list(req.tokens))[:-1]
            state = self._reprefill_slot(params, cfg, state, slot, hist)
        return state

    def warm_reprefill(self, params, cfg: ModelConfig) -> None:
        """Compile :meth:`reprefill_state`'s programs for ``cfg`` by
        re-prefilling one token into the first two slots of a fresh state
        (discarded: the first insert takes a fresh state, later ones the
        state an insert returned), so a hop that meets live sessions
        compiles nothing."""
        if self.kv_layout == "paged" and not paged_supported(cfg):
            return                     # the hop refuses such a target
        state = self.fresh_state(cfg)
        for slot in range(min(2, self.slots)):
            state = self._reprefill_slot(params, cfg, state, slot, [0])
        jax.block_until_ready(state)

    def _reprefill_slot(self, params, cfg: ModelConfig, state, slot: int,
                        hist: List[int]):
        prefill_one, _, insert = make_serving_fns(
            cfg, self._cap_for(cfg), self.kv_layout, self.keep_residual,
            self.paged_kernel(cfg))
        toks = np.zeros((1, self.max_len), np.int32)
        toks[0, :len(hist)] = hist
        out = prefill_one(params, jnp.asarray(toks), jnp.asarray(len(hist)))
        return insert(self._sync_paged(state), out[1],
                      jnp.asarray(len(hist), jnp.int32),
                      jnp.asarray(slot, jnp.int32))

    def _sync_paged(self, state):
        if self.alloc is not None:
            return {**state, "pages": self.alloc.device_table()}
        return state

    # -- depth-replay fast path ---------------------------------------------
    def replay_ready(self) -> bool:
        """True when every live slot's preserved residual stream covers its
        whole history (a post-hop slot only recovers coverage once it is
        re-admitted, since pre-hop residuals describe the old model)."""
        return (self.keep_residual and self.resid is not None
                and all(self.resid_from[i] == 0
                        for i, r in enumerate(self.slot_req)
                        if r is not None))
