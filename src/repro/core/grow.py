"""High-level growth API + the LiGO training phase (paper §3.2, "Training").

``grow(...)`` covers every method compared in the paper:

- method="ligo":  init LiGO params, run ``ligo_steps`` of SGD-with-momentum on
  the task loss *through* the growth operator (Θ_small frozen), materialise
  Θ_large. The 100-step default matches the paper (Table 3 shows savings are
  flat in [100, 1000]).
- method="stackbert" | "interpolation" | "net2net" | "bert2bert": classical
  operators, no learning.
- method="random": fresh init of the big model (the from-scratch baseline).

The LiGO phase runs as a **jitted, buffer-donated ``lax.scan``**: batches are
prefetched and stacked per chunk, the (grad → momentum → SGD) step is scanned
inside one compiled program, and the growth operator itself is applied through
the compiled :class:`repro.core.plan.GrowthPlan` — so the phase traces exactly
once and never re-resolves expanders per step (asserted by
``TRACE_COUNTS["train_ligo"]`` in the tests).

Works under pjit: pass ``mesh``-sharded small params and a data iterator that
yields global batches; apply_ligo is pure einsums so GSPMD shards the growth.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ligo import apply_ligo, init_ligo_params
from repro.core import operators as ops
from repro.distributed.sharding import current_mesh, mesh_attrs
from repro.models.losses import loss_fn
from repro.models.model import init_params
from repro import obs

# How many times each compiled region was (re-)traced — tests assert the LiGO
# phase compiles once regardless of step count. Locked counter group
# ("core.traces" in the obs registry): the hop's background grow thread may
# trace concurrently with the decode loop.
TRACE_COUNTS: obs.CounterGroup = obs.counter_group("core.traces")


def saved_activation_bytes(cfg: ModelConfig, batch: int, seq: int,
                           sizes: Dict[str, int]) -> int:
    """What one device keeps of ``cfg``'s activations for a backward pass
    over a ``batch`` x ``seq`` batch, with the batch split over the mesh
    axis ``data`` and the heads and feed-forward width over ``model``
    (``sizes``: axis sizes; a dim that does not divide stays whole): per
    layer and token, f32 attention scores and probabilities (1.5 copies),
    about eight model-wide and three feed-forward-wide f32 activations."""
    def per(n, axis):
        k = sizes.get(axis, 1)
        return n // k if n % k == 0 else n
    b, h, ff = per(batch, "data"), per(cfg.n_heads, "model"), \
        per(cfg.d_ff, "model")
    per_token = 6 * h * seq + 4 * (8 * cfg.d_model + 3 * ff)
    return cfg.n_layers * b * seq * per_token


def ligo_remat_needed(cfg2: ModelConfig, batch_shape,
                      bytes_limit: Optional[int] = None) -> bool:
    """Recompute the grown layers in the LiGO phase's backward pass when
    keeping their activations would take more than half of a device's
    memory (``bytes_limit``; by default the first local device's, and no
    recomputation where the backend reports none, as on the CPU). The
    ambient mesh gives the split of the batch and of the layers."""
    if bytes_limit is None:
        stats = jax.local_devices()[0].memory_stats() or {}
        bytes_limit = stats.get("bytes_limit")
    if not bytes_limit:
        return False
    mesh = current_mesh()
    sizes = dict(mesh.shape) if mesh is not None else {}
    return saved_activation_bytes(cfg2, int(batch_shape[0]),
                                  int(batch_shape[1]), sizes) \
        > bytes_limit / 2


def ligo_loss(ligo, small_params, cfg1: ModelConfig, cfg2: ModelConfig,
              batch, *, loss_chunk: int = 0, engine: str = "plan"
              ) -> jax.Array:
    """The task loss of the grown model; its backward recomputes the grown
    layers where :func:`ligo_remat_needed` says so from the shapes."""
    x = next((batch[k] for k in ("tokens", "frames", "patches")
              if k in batch), None)           # (batch, positions, ...)
    remat = x is not None and ligo_remat_needed(cfg2, x.shape)
    big = apply_ligo(ligo, small_params, cfg1, cfg2, engine=engine)
    loss, _ = loss_fn(big, cfg2, batch, loss_chunk=loss_chunk, remat=remat)
    return loss


def _stack_batches(batches):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def _ligo_phase_id(cfg1: ModelConfig, cfg2: ModelConfig, steps: int,
                   lr: float, momentum: float,
                   phase_meta: Optional[Dict]) -> Dict:
    """Identity stamped on (and validated against) every phase checkpoint:
    a carry from a different hop, budget or schedule must never be resumed
    into this phase — it is silently ignored and the phase starts fresh."""
    pid = {"ligo_cfg1": cfg1.config_hash(), "ligo_cfg2": cfg2.config_hash(),
           "ligo_steps": int(steps), "ligo_lr": float(lr),
           "ligo_momentum": float(momentum)}
    pid.update(phase_meta or {})
    return pid


def train_ligo(ligo, small_params, cfg1: ModelConfig, cfg2: ModelConfig,
               data_it: Iterator[Dict[str, jax.Array]], *,
               steps: int = 100, lr: float = 1e-3, momentum: float = 0.9,
               loss_chunk: int = 0, jit: bool = True,
               log_every: int = 0, engine: str = "plan",
               scan_chunk: int = 0, phase_ckpt=None,
               phase_meta: Optional[Dict] = None,
               checkpoint_every_chunks: int = 1,
               fail_at: Optional[int] = None,
               ledger=None,
               ledger_ctx: Optional[Dict] = None) -> Tuple[Dict, list]:
    """The ~100-step SGD phase optimising only the LiGO parameters.

    The phase runs as chunks of ``scan_chunk`` steps: each chunk prefetches
    + stacks its batches and executes a single jitted ``lax.scan`` over the
    (grad, momentum, SGD) step, with the (ligo, momentum) carry buffers
    donated between chunks. The default picks the largest divisor of
    ``steps`` ≤ 32, so batch memory stays bounded and every chunk has the
    same shape — one trace total (expander resolution and growth-plan work
    happen at trace time only). An explicit ``scan_chunk`` that does not
    divide ``steps`` still works but the ragged final chunk compiles a
    second program.

    **Elastic phase** (``phase_ckpt``): pass a
    :class:`repro.checkpoint.CheckpointManager` and the
    ``(ligo, momentum, step)`` scan carry is checkpointed (async) every
    ``checkpoint_every_chunks`` chunk boundaries, stamped with the phase
    identity (config pair, budget, schedule, plus the caller's
    ``phase_meta`` — the trajectory runner adds its trajectory hash and
    stage index). A later call with the same arguments restores the carry
    and continues from the last finished chunk — on any mesh, since the
    carry is replicated — instead of redoing the phase from step 0. A
    checkpoint whose identity does not match is ignored (fresh start), so a
    stale phase directory from an earlier hop can never corrupt a new one.
    Resume consumes the batch iterator deterministically: the first
    ``start`` batches are drawn and discarded so step ``k``'s batch is the
    same in the resumed and uninterrupted runs.

    ``fail_at`` is a chaos-testing knob: after the first chunk boundary
    ``>= fail_at`` (checkpoint durably written first), the phase raises —
    the deterministic mid-phase "kill" used by the tests and the CI
    kill+resume smoke.

    **Ledger** (``ledger``, a :class:`repro.obs.ledger.RunLedger`): every
    LiGO step lands as a ``phase="ligo"`` step record — loss from the
    scanned chunk, FLOPs from the compile-time measured-cost pass over
    the chunk program (the trip-count-corrected read-back of the scan
    body; modelled ``6·N₂·B·S`` otherwise). On an elastic resume the
    already-run steps are *re-emitted* from the phase checkpoint's saved
    losses (their original walls are gone, so ``wall_ms`` is 0 — the one
    field the ledger identity contract excludes), so the resumed ledger
    is record-for-record identical to an uninterrupted run as long as
    the resume lands on a chunk boundary of the same chunk size (the
    elastic contract). ``ledger_ctx`` carries ``{"stage", "n_devices"}``
    from the trajectory runner.
    """
    grad_fn = jax.value_and_grad(
        partial(ligo_loss, cfg1=cfg1, cfg2=cfg2, loss_chunk=loss_chunk,
                engine=engine),
        argnums=0)

    def sgd_step(small, carry, batch):
        ligo, mom = carry
        loss, g = grad_fn(ligo, small, batch=batch)
        mom = jax.tree.map(lambda m, gg: momentum * m + gg, mom, g)
        ligo = jax.tree.map(lambda p, m: p - lr * m, ligo, mom)
        return (ligo, mom), loss

    # the source params are an argument, not a closure: captured, they
    # would be baked into the chunk program as constants
    def run_chunk(ligo, mom, small, batches):
        TRACE_COUNTS.inc("train_ligo")
        (ligo, mom), losses = jax.lax.scan(partial(sgd_step, small),
                                           (ligo, mom), batches)
        return ligo, mom, losses

    if steps <= 0:
        return ligo, []
    if scan_chunk > 0:
        chunk = scan_chunk
    else:
        # equal chunks (single trace) from a divisor in [16, 32] when one
        # exists; divisor-poor step counts (primes) fall back to full
        # 32-chunks + one ragged tail — a second trace, but the dispatch
        # amortisation is kept.
        chunk = min(steps, 32)
        while chunk > 16 and steps % chunk:
            chunk -= 1
        if steps % chunk:
            chunk = min(steps, 32)

    # ---- elastic-phase restore ------------------------------------------
    mom = jax.tree.map(jnp.zeros_like, ligo)
    losses: list = []
    start = 0
    pid = _ligo_phase_id(cfg1, cfg2, steps, lr, momentum, phase_meta)
    if phase_ckpt is not None:
        saved = phase_ckpt.latest_meta()
        if saved is not None and all(saved.get(k) == v
                                     for k, v in pid.items()):
            tmpl = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"ligo": ligo, "mom": mom})
            state, _ = phase_ckpt.restore(phase_ckpt.latest_step(), tmpl)
            ligo, mom = state["ligo"], state["mom"]
            start = int(saved["phase_step"])
            losses = [float(x) for x in saved.get("losses", [])][:start]
            print(f"[ligo] resumed LiGO phase at step {start}/{steps}",
                  flush=True)
            obs.event("ligo.resume", step=start, steps=steps)

    if jit:
        # Donating the (ligo, momentum) carry keeps the phase zero-copy
        # between chunks; CPU jax warns on donation, so gate it. The first
        # chunk would otherwise donate (delete) the *caller's* operator
        # buffers, so hand it an owned copy.
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        run_chunk = jax.jit(run_chunk, donate_argnums=donate)
        if donate:
            ligo = jax.tree.map(jnp.array, ligo)
            mom = jax.tree.map(jnp.array, mom)

    peek = None
    for _ in range(start):          # deterministic resume: skip spent batches
        b = next(data_it)
        if peek is None:
            peek = b                # shape witness for the measured pass

    # ---- compute ledger: measured-cost pass + per-step records ----------
    led_stage = int((ledger_ctx or {}).get("stage", 0))
    led_nd = int((ledger_ctx or {}).get("n_devices", 1))
    led_state = {"tokens": None, "fps_model": None, "meas_fps": None}

    def _ledger_prepare(batch_tree, n_chunk: int) -> None:
        """Model + (when jitted) measure the chunk program, once per phase.
        ``batch_tree`` is one un-stacked batch; lowering only needs shapes,
        so the resume path reuses a discarded batch as the witness."""
        from repro.roofline import train_flops_per_step
        leaf = batch_tree.get("tokens") if isinstance(batch_tree, dict) \
            else None
        if leaf is None:
            leaf = max(jax.tree.leaves(batch_tree), key=lambda x: x.ndim)
        bsz, seq = int(leaf.shape[0]), int(leaf.shape[1])
        led_state["tokens"] = float(bsz * seq)
        led_state["fps_model"] = train_flops_per_step(cfg2, bsz, seq)
        if jit and n_chunk > 0:
            from repro.obs import costs
            stacked = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((n_chunk,) + x.shape,
                                               x.dtype), batch_tree)
            m = costs.measure_jitted(
                f"ligo_chunk[{cfg2.name}]", run_chunk, ligo, mom,
                small_params, stacked,
                modelled_flops=led_state["fps_model"] * n_chunk,
                n_devices=led_nd, per_call_units=n_chunk)
            if m is not None:
                led_state["meas_fps"] = m["flops_per_unit"]

    def _ledger_steps(first_step: int, step_losses, wall_ms_each: float
                      ) -> None:
        for j, lv in enumerate(step_losses):
            ledger.record_step(
                phase="ligo", stage=led_stage, arch=cfg2.name,
                step=first_step + j, loss=lv, tokens=led_state["tokens"],
                wall_ms=wall_ms_each,
                flops_modelled=led_state["fps_model"],
                flops_measured=led_state["meas_fps"])

    if ledger is not None and start > 0:
        # the runner truncated the ledger to the last *trajectory*
        # checkpoint (which predates this hop); rebuild the already-run
        # phase records from the phase checkpoint's losses
        _ledger_prepare(peek, min(chunk, steps - start))
        _ledger_steps(0, losses, 0.0)

    done = start
    chunks_done = 0
    h_chunk = obs.histogram("ligo.chunk_ms")
    h_ckpt = obs.histogram("ligo.checkpoint_ms")
    where = mesh_attrs()
    while done < steps:
        n = min(chunk, steps - done)
        # host-boundary timing: float(l) on the losses forces the sync, so
        # the span wall covers the whole compiled chunk, never intrudes on it
        with obs.span("ligo.chunk", start=done, n=n, **where) as sp_chunk:
            with obs.span("ligo.batches", n=n):
                raw = [next(data_it) for _ in range(n)]
                if ledger is not None and led_state["tokens"] is None:
                    _ledger_prepare(raw[0], n)
                batches = _stack_batches(raw)
            # trace, lower, compile or cache lookup, enqueue; ``traced``
            # counts the chunk program's traces this launch made
            with obs.span("ligo.launch", n=n) as sp_launch:
                traced = TRACE_COUNTS["train_ligo"]
                ligo, mom, chunk_losses = run_chunk(ligo, mom, small_params,
                                                    batches)
                sp_launch.attrs["traced"] = (TRACE_COUNTS["train_ligo"]
                                             - traced)
            with obs.span("ligo.sync", n=n):
                chunk_losses = [float(l) for l in chunk_losses]
            losses.extend(chunk_losses)
        h_chunk.observe(sp_chunk.dur_ms or 0.0)
        if ledger is not None:
            _ledger_steps(done, chunk_losses, (sp_chunk.dur_ms or 0.0) / n)
        done += n
        chunks_done += 1
        failing = fail_at is not None and fail_at <= done < steps
        if (phase_ckpt is not None and done < steps
                and (chunks_done % max(checkpoint_every_chunks, 1) == 0
                     or failing)):
            # double-buffered async snapshot: jnp.copy enqueues a
            # device-to-device copy (ordered before any later op touching
            # the carry, so the next chunk may donate these buffers) and
            # the device->host transfer runs on the write thread — the
            # chunk loop never blocks on the copy-out. An injected failure
            # forces the save even off-cadence: the chaos contract is
            # "checkpoint durably written, then die".
            with obs.span("ligo.checkpoint", step=done) as sp_ckpt:
                phase_ckpt.save(done, {"ligo": ligo, "mom": mom},
                                {**pid, "phase_step": done, "losses": losses},
                                snapshot="device")
            h_ckpt.observe(sp_ckpt.dur_ms or 0.0)
        if failing:
            if phase_ckpt is not None:
                phase_ckpt.wait()          # the injected kill must be durable
            raise RuntimeError(
                f"injected LiGO-phase failure at step {done}/{steps}")
        if log_every:
            for s in range(done - n, done):
                if s % log_every == 0:
                    print(f"[ligo] step {s:4d} loss {losses[s]:.4f}")
    if phase_ckpt is not None:
        phase_ckpt.wait()
    return ligo, losses


def _validate_opt_state(opt_state, small_params) -> None:
    """Refuse optimizer state that cannot ride a growth operator — with a
    message, not a shape crash deep inside the growth plan.

    Checkpoints written before optimizer-state growth existed (or by a
    different trainer) lack the ``AdamWState`` layout: no ``count`` leaf, no
    ``m``/``v`` moment trees, or moments that do not mirror the source
    parameter tree. Any of those used to die as an opaque pytree/shape error
    inside ``apply_ligo``; surface the actual problem instead.
    """
    if opt_state is None:
        return
    missing = [f for f in ("m", "v", "count")
               if getattr(opt_state, f, None) is None]
    if missing:
        raise ValueError(
            f"opt_state is missing {missing} — not a grow-compatible "
            "AdamWState. This optimizer state predates grow_state (or was "
            "written by an older trainer). Re-checkpoint with the current "
            "trainer, or start the grown stage fresh with "
            "grow_optimizer=False / opt_state=None.")
    if small_params is None:
        return
    want = jax.tree.structure(small_params)
    for name in ("m", "v"):
        got = jax.tree.structure(getattr(opt_state, name))
        if got != want:
            raise ValueError(
                f"opt_state.{name} does not mirror the source parameter "
                f"tree ({got} vs {want}) — the checkpointed optimizer "
                "state predates grow_state or belongs to a different "
                "architecture. Re-checkpoint, or pass "
                "grow_optimizer=False to reset moments after the hop.")


def grow(small_params, cfg1: ModelConfig, cfg2: ModelConfig, *,
         method: str = "ligo", key: Optional[jax.Array] = None,
         data_it: Optional[Iterator] = None, ligo_steps: int = 100,
         ligo_lr: float = 1e-3, ligo_momentum: float = 0.9,
         loss_chunk: int = 0, depth_init: str = "stack",
         engine: str = "plan", opt_state=None, grow_optimizer: bool = True,
         apply: bool = True, ligo_ckpt=None,
         ligo_meta: Optional[Dict] = None, ligo_scan_chunk: int = 0,
         ligo_fail_at: Optional[int] = None,
         ligo_ledger=None, ligo_ledger_ctx: Optional[Dict] = None,
         ) -> Tuple[Optional[Dict], Dict[str, Any]]:
    """Grow Θ_small → Θ_large. Returns (big_params, info).

    When an AdamW ``opt_state`` for the small model is passed, the grown
    state lands in ``info["opt_state"]``: moments carried through the
    learned/classical operator with method-correct semantics (first moment
    linear, second moment through the squared operator, schedule count
    preserved — :func:`repro.optim.grow_adamw_state`), so post-growth
    training *continues* instead of re-warming. ``method="random"`` (or
    ``grow_optimizer=False``) has no operator to carry state through and
    returns a fresh ``adamw_init`` of the big tree.

    ``apply=False`` builds (and for LiGO, trains) the operator but skips
    materialising Θ_large and the optimizer growth — ``(None, info)`` with
    ``info["operator"]`` set. Multi-hop callers (skip-stage composition in
    the trajectory runner) use it to collect per-hop operators and apply
    their analytic composition once.

    ``ligo_ckpt``/``ligo_meta``/``ligo_scan_chunk``/``ligo_fail_at`` make
    the LiGO phase elastic — threaded straight into :func:`train_ligo`'s
    phase-checkpointing (see its docstring) — and
    ``ligo_ledger``/``ligo_ledger_ctx`` give the phase's per-step records
    to the compute ledger the same way. The phase recomputes the grown
    layers in its backward pass where their activations would not fit
    beside it (:func:`ligo_remat_needed`, from the shapes).

    Under an ambient mesh (``jax.set_mesh``) every program of the hop runs
    sharded: the phase's chunk program over the batches as they are
    sharded, and the growth of the parameters and moments through the
    plan's sharded executor, whose outputs land as ``params_pspecs``
    prescribes. The ``grow`` span and each ``ligo.chunk`` span carry the
    mesh (``mesh``, ``devices``).
    """
    with obs.span("grow", method=method, src=cfg1.name, dst=cfg2.name,
                  **mesh_attrs()):
        key = key if key is not None else jax.random.PRNGKey(0)
        info: Dict[str, Any] = {"method": method}
        _validate_opt_state(opt_state, small_params)
        if method == "random":
            big = init_params(cfg2, key)
            if opt_state is not None:
                from repro.optim import adamw_init
                info["opt_state"] = adamw_init(big)
            return big, info
        if method == "stackbert":
            op = ops.stackbert_operator(cfg1, cfg2, key=key)
        elif method == "interpolation":
            op = ops.interpolation_operator(cfg1, cfg2, key=key)
        elif method == "net2net":
            op = ops.net2net_operator(key, cfg1, cfg2)
        elif method == "bert2bert":
            op = ops.bert2bert_operator(key, cfg1, cfg2)
        elif method == "lemon":
            op = ops.lemon_operator(cfg1, cfg2)
        elif method == "upcycle":
            from repro.core.upcycle import upcycle_operator
            op = upcycle_operator(cfg1, cfg2)
        elif method == "gqa_merge":
            op = ops.gqa_merge_operator(cfg1, cfg2)
        elif method == "ligo":
            with obs.span("ligo.init"):
                op = init_ligo_params(key, cfg1, cfg2, depth_init=depth_init)
            if ligo_steps and data_it is not None:
                with obs.span("ligo.phase", steps=ligo_steps):
                    op, losses = train_ligo(
                        op, small_params, cfg1, cfg2, data_it,
                        steps=ligo_steps, lr=ligo_lr, momentum=ligo_momentum,
                        loss_chunk=loss_chunk, engine=engine,
                        scan_chunk=ligo_scan_chunk, phase_ckpt=ligo_ckpt,
                        phase_meta=ligo_meta, fail_at=ligo_fail_at,
                        ledger=ligo_ledger, ledger_ctx=ligo_ledger_ctx)
                info["ligo_losses"] = losses
        else:
            raise ValueError(method)
        info["operator"] = op
        if not apply:
            return None, info
        with obs.span("grow.params"):
            big = apply_ligo(op, small_params, cfg1, cfg2, engine=engine)
        if opt_state is not None:
            if grow_optimizer:
                from repro.optim import grow_adamw_state
                with obs.span("grow.moments"):
                    info["opt_state"] = grow_adamw_state(
                        opt_state, op, cfg1, cfg2, engine=engine)
            else:
                from repro.optim import adamw_init
                info["opt_state"] = adamw_init(big)
        return big, info
