"""Batched serving driver: prefill a batch of prompts, decode new tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \\
        --batch 4 --prompt-len 64 --gen 32

``--ckpt DIR`` serves trained weights: the newest checkpoint restores
sharded through ``CheckpointManager`` (arrays ``device_put`` with the
``params_pspecs`` shardings for the serving mesh); without it the driver
serves fresh ``init_params`` at smoke scale.

Growth-time elastic serving: ``--grow-to <arch>`` (or the shorthand ``2x``
for a doubled-depth/1.5×-width target of the same family) hot-grows the
loaded checkpoint at startup through the compiled GrowthPlan executor
(:func:`repro.core.plan_for` — cached expanders, batched leaf groups, fused
Pallas blend-expand on TPU), then serves the *grown* architecture. The plan
executor is memoised, so repeated growth of the same (cfg1, cfg2) pair pays
a single dispatch (~ms), cheap enough to run per serving process. The growth
itself runs *sharded* under the serving mesh (in/out shardings from
``params_pspecs``), so growing to an 8B+ target never funnels the tree
through one device.

**Zero-downtime live growth**: ``--live-grow-at N`` serves through the
continuous-batching engine (``repro.serving``) and hops to the ``--grow-to``
target after N decode steps *while serving*: grown params materialise
double-buffered in the background, live sessions' KV caches migrate
(in-place growth when the operator is lossless, re-prefill otherwise), and
the buffers swap atomically between decode steps. A failed hop (inject one
with ``--fail-at-hop grow|cache-grow|swap|hang``) rolls back and retries
with backoff; in-flight requests never drop either way. A hop that gives up
after its retries, or a dropped request, makes the driver exit non-zero.

On the production mesh, params are FSDP+TP sharded and the KV cache is
sequence- or head-sharded per repro.distributed.sharding.state_pspecs; on CPU
the same code runs on host devices at smoke scale.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, grow_target, moe_target, smoke_config
from repro import obs
from repro.data import gen_tokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import decode_step, init_params, prefill


def _target_chain(cfg, target: str, *, smoke: bool):
    """Resolve a (possibly multi-hop) ``--grow-to`` spec into a config chain.

    ``target`` is a comma-separated list of hops, each either a registry
    arch name (smoke-reduced when serving in smoke mode) or ``"Nx"`` with N
    a power of two — the *cumulative* grow_target multiple relative to the
    most recent explicitly-named arch (the serving arch when none was
    named), so ``2x,4x`` means base → grow_target(base) →
    grow_target(grow_target(base)), and an arch-name hop restarts the
    multiple at 1x of that arch.
    """
    chain, cur, cum = [], cfg, 1
    for tok in target.split(","):
        tok = tok.strip()
        if tok == "moe":                 # dense→MoE upcycling target
            cur = moe_target(cur)
            cum = 1
        elif tok.endswith("x") and tok[:-1].isdigit():
            n = int(tok[:-1])
            if n <= cum or n % cum or ((n // cum) & (n // cum - 1)):
                raise SystemExit(
                    f"--grow-to: '{tok}' after {cum}x — cumulative 'Nx' "
                    f"hops must be increasing powers of two (e.g. 2x,4x)")
            for _ in range((n // cum).bit_length() - 1):
                cur = grow_target(cur)
            cum = n
        else:
            cur = get_config(tok)
            if smoke:
                cur = smoke_config(cur)
            cum = 1                     # 'Nx' counts restart at this arch
        chain.append(cur)
    return chain


def hot_grow(params, cfg, target: str, *, smoke: bool = False, seed: int = 1,
             mesh=None):
    """Grow ``params`` (cfg) to the ``target`` architecture(s) at startup.

    ``target`` is a single hop (registry arch name, or ``"2x"`` for
    ``grow_target(cfg)``) or a comma-separated multi-hop list (e.g.
    ``2x,4x`` — see :func:`_target_chain`). Multi-hop targets compose their
    per-hop operators analytically (:func:`repro.core.compose_chain`) into
    ONE ``cfg → final`` operator executed by a single fused GrowthPlan:
    no intermediate model is ever materialised and no intermediate
    checkpoint written. Returns ``(grown_params, final_cfg)``. The memoised
    executor makes repeated growth of the same chain one compiled dispatch.

    ``mesh`` defaults to the ambient mesh (we run inside ``set_mesh`` in
    ``main``): the growth executes **sharded** — in/out shardings follow
    ``params_pspecs``, the LiGO expanders ride replicated — so the grown
    tree lands already laid out for the sharded decode path and 8B+ targets
    never materialise on one device.
    """
    from repro.core import compose_chain, init_ligo_params, plan_for
    from repro.distributed.sharding import current_mesh
    if mesh is None:
        mesh = current_mesh()
    chain = [cfg] + _target_chain(cfg, target, smoke=smoke)
    ops = [init_ligo_params(jax.random.PRNGKey(seed + i), a, b)
           for i, (a, b) in enumerate(zip(chain[:-1], chain[1:]))]
    ligo = compose_chain(ops, chain)
    cfg2 = chain[-1]
    t0 = time.perf_counter()
    grown = plan_for(cfg, cfg2, params).executor(mesh=mesh)(ligo, params)
    jax.block_until_ready(jax.tree.leaves(grown)[0])
    ndev = 1 if mesh is None else mesh.size
    hops = ("" if len(ops) == 1
            else f" via {len(ops)} composed hops (one fused apply)")
    print(f"[serve] hot-grew {cfg.name} -> {cfg2.name} "
          f"({cfg.n_layers}L/{cfg.d_model}d -> {cfg2.n_layers}L/"
          f"{cfg2.d_model}d) on {ndev} device(s) in "
          f"{(time.perf_counter() - t0) * 1e3:.1f} ms{hops}")
    return grown, cfg2


def _restore_ckpt(ckpt_dir: str, cfg, mesh):
    """Restore the newest checkpoint in ``ckpt_dir`` sharded for serving.

    Arrays land ``device_put`` with the ``params_pspecs`` shardings for this
    mesh (elastic: the save-time mesh is irrelevant). Accepts both the
    trainer layout ``{"params", "opt"}`` (optimizer state ignored) and a
    bare params tree."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed.sharding import named_shardings, params_pspecs
    mgr = CheckpointManager(ckpt_dir)
    step = mgr.latest_step()
    if step is None:
        raise SystemExit(f"--ckpt {ckpt_dir}: no checkpoint found")
    tmpl = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    ps = params_pspecs(tmpl, model_size=mesh.shape.get("model", 1),
                       dp_size=mesh.shape.get("data", 1))
    sh = named_shardings(ps, mesh)
    try:
        tree, meta = mgr.restore(step, {"params": tmpl}, {"params": sh})
        params = tree["params"]
    except KeyError:
        params, meta = mgr.restore(step, tmpl, sh)
    print(f"[serve] restored step-{step} checkpoint from {ckpt_dir} "
          f"for {cfg.name} (sharded via params_pspecs)")
    return params


def _serve_live(args, cfg, params, mesh):
    """Engine-backed serving with a mid-serve hop (``--live-grow-at``).

    Returns the run's outcome: request counts, whether the hop completed
    and after how many attempts, the served architecture and token count."""
    from repro.core import compose_chain, init_ligo_params
    from repro.serving import HopController, ServingEngine
    if cfg.modality != "text":
        raise SystemExit(f"--live-grow-at: {cfg.name} is not a token model")
    if args.hop_operator == "lemon":
        # Lossless hop: double d_ff at fixed d_model/d_head/heads — the one
        # expansion LEMON zero-padding supports unconditionally (GQA
        # included). The grown model is bitwise the same function, so the
        # cache grows in place and a resident drafter's proposals are
        # accepted wholesale (the spec-decode-through-hop smoke relies on
        # this). --grow-to is ignored on this path.
        from repro.core.operators import lemon_operator
        cfg2 = cfg.scaled(name=f"{cfg.name}-ff2", d_ff=cfg.d_ff * 2)
        ligo = lemon_operator(cfg, cfg2)
    elif args.hop_operator == "upcycle":
        # Dense→MoE upcycling as a live hop: every expert starts as a copy
        # of the dense FFN, the router starts uniform — the upcycled model
        # is the same function at init (lossless), so the K/V cache grows in
        # place (attention is untouched by the hop) and a resident drafter
        # keeps 100% acceptance. --grow-to names the MoE target (default:
        # moe_target of the serving arch).
        from repro.core.upcycle import upcycle_operator
        if args.grow_to:
            tail = _target_chain(cfg, args.grow_to, smoke=args.smoke)
            if len(tail) != 1:
                raise SystemExit("--hop-operator upcycle takes a single-hop "
                                 "--grow-to target")
            cfg2 = tail[0]
        else:
            cfg2 = moe_target(cfg)
        ligo = upcycle_operator(cfg, cfg2)
    else:
        chain = [cfg] + _target_chain(cfg, args.grow_to or "2x",
                                      smoke=args.smoke)
        ops = [init_ligo_params(jax.random.PRNGKey(1 + i), a, b)
               for i, (a, b) in enumerate(zip(chain[:-1], chain[1:]))]
        ligo = compose_chain(ops, chain)
        cfg2 = chain[-1]

    engine = ServingEngine(params, cfg, slots=args.batch,
                           prompt_budget=args.prompt_len,
                           gen_budget=args.gen,
                           queue_capacity=args.queue_cap, mesh=mesh,
                           kv_layout=args.kv_layout,
                           block_size=args.block_size,
                           pool_blocks=args.kv_pool_blocks,
                           temperature=args.temperature, top_p=args.top_p,
                           seed=args.seed, spec_k=args.speculative)
    hop = HopController(engine, cfg2, ligo, cache_mode=args.cache_mode,
                        fail_at=args.fail_at_hop, retries=args.hop_retries,
                        timeout=args.hop_timeout,
                        background=not args.hop_sync)
    hop.warm()                     # pre-compile the grow + seed the watchdog
    n_req = args.requests or args.batch * 2
    rng = np.random.RandomState(0)
    prompts = np.asarray(gen_tokens(0, 0, n_req, args.prompt_len,
                                    cfg.vocab_size))
    for r in range(n_req):
        plen = int(rng.randint(max(2, args.prompt_len // 2),
                               args.prompt_len + 1))
        engine.submit(list(prompts[r, :plen]), max_new=args.gen)

    t0 = time.perf_counter()

    def on_step(eng):
        if eng.decode_steps >= args.live_grow_at and hop.attempts == 0:
            hop.begin()
        if hop.attempts:
            hop.poll()

    engine.run(on_step=on_step)
    if hop.attempts == 0:        # queue drained before the trigger step
        hop.begin()
    while not hop.poll():
        time.sleep(0.002)
    wall = time.perf_counter() - t0

    c = engine.counts()
    total = sum(len(r.tokens) for r in engine.requests
                if r.status == "done")
    p50, p99 = engine.decode_step_percentiles(50, 99)
    if np.isnan(p50):
        p50 = p99 = 0.0
    print(f"[serve] live-hop serve: arch={cfg.name} -> "
          f"{cfg2.name if hop.completed else cfg.name} slots={args.batch} "
          f"requests={n_req}")
    # Report the layout actually served — the engine may have fallen back
    # from a requested paged layout (windowed/seqmix: no paged support).
    fb = (f" (FALLBACK from requested "
          f"'{engine.kv_layout_requested}': paged KV unsupported for "
          f"family={cfg.family!r}, window={cfg.window})"
          if engine.kv_fallback else "")
    print(f"[serve] kv layout: {engine.kv_layout}{fb}")
    print(f"[serve] {c['done']} done, {c['rejected']} rejected, "
          f"{c['dropped']} dropped | hop "
          f"{'complete' if hop.completed else 'FAILED (gave up)'} "
          f"(cache: {hop.cache_path}, attempts {hop.attempts})")
    print(f"[serve] {total} tokens in {wall:.2f} s | "
          f"{total / max(wall, 1e-9):.1f} tok/s | decode p50 "
          f"{p50:.1f} ms p99 {p99:.1f} ms (through the hop)")
    if args.speculative > 0:
        st = engine.spec_stats
        if st.get("rounds"):
            print(f"[spec] acceptance {st['accepted']}/{st['drafted']} "
                  f"drafted ({st['accepted'] / max(1, st['drafted']):.0%}, "
                  f"first round {st.get('first_round_acc', 0.0):.0%}) | "
                  f"K={engine.spec_k} drafter={st.get('drafter')} | est "
                  f"speedup {st.get('est_speedup', 0.0):.2f}x"
                  + (f" | disabled: {st['disabled']}" if st.get("disabled")
                     else ""))
        else:
            print("[spec] acceptance n/a (no speculative rounds ran — "
                  "drafter never adopted or queue drained pre-hop)")
    if engine.alloc is not None:
        a = engine.alloc
        pool = engine.state["caches"]["k"]   # (L, n_blocks, bs, KV·dh)
        elt = jnp.dtype(pool.dtype).itemsize
        block_bytes = 2 * pool.shape[0] * int(np.prod(pool.shape[2:])) * elt
        dense_bytes = block_bytes // a.block_size * engine.cap
        print(f"[paged] peak {a.peak_blocks} blocks | "
              f"{a.bytes_per_slot(block_bytes) / 1024:.1f} KiB/slot vs "
              f"{dense_bytes / 1024:.1f} KiB/slot dense")
    return {"requests": n_req, "done": c["done"], "dropped": c["dropped"],
            "rejected": c["rejected"], "hop_completed": hop.completed,
            "hop_attempts": hop.attempts, "cache_path": hop.cache_path,
            "arch": (cfg2 if hop.completed else cfg).name, "tokens": total,
            "wall_s": wall}


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv``) and serve. Returns the live
    path's outcome (see :func:`_serve_live`), or None for the batch path;
    exits non-zero when the live hop gave up or a request was dropped."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="live-path sampling temperature (0 = greedy; "
                         "sampling runs a fixed per-slot Philox chain keyed "
                         "by --seed, so runs are reproducible)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed (live path)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="after the live hop, keep the pre-hop model "
                         "resident as a drafter: draft K tokens/slot per "
                         "round with the small model, verify all K in one "
                         "batched launch of the grown one (greedy output is "
                         "bit-equal to vanilla greedy; auto-disables when "
                         "the measured speedup estimate drops below 1)")
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "dense"],
                    help="live-path KV cache layout: paged = fixed-size "
                         "blocks + per-slot page tables over a shared pool "
                         "(mixed-length slots stop paying max_len); dense = "
                         "one max_len row per slot (the oracle)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV block size (tokens per block)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="paged KV pool size in blocks (default: every slot "
                         "can reach max_len). Smaller pools create real "
                         "admission pressure: requests defer at the door "
                         "(never drop) until their worst case fits")
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="serve the newest checkpoint in DIR (restored "
                         "sharded via params_pspecs) instead of init_params")
    ap.add_argument("--live-grow-at", type=int, default=None, metavar="N",
                    help="serve through the continuous-batching engine and "
                         "hop to the --grow-to target after N decode steps "
                         "WITHOUT stopping: params grow double-buffered in "
                         "the background, live KV caches migrate, buffers "
                         "swap between decode steps")
    ap.add_argument("--fail-at-hop", default=None,
                    choices=["grow", "cache-grow", "swap", "hang"],
                    help="chaos hook: inject a one-shot failure at this hop "
                         "stage (the hop rolls back, then retries clean)")
    ap.add_argument("--hop-retries", type=int, default=2)
    ap.add_argument("--hop-timeout", type=float, default=120.0,
                    help="hop watchdog hard budget (seconds) for the grow "
                         "stage")
    ap.add_argument("--hop-sync", action="store_true",
                    help="run the grow stage synchronously instead of "
                         "overlapped with decoding (deterministic timing)")
    ap.add_argument("--cache-mode", default="auto",
                    choices=["auto", "grow", "replay", "reprefill"],
                    help="live-hop KV-cache migration: auto = in-place "
                         "growth iff the operator is provably lossless, "
                         "else new-layer replay from the preserved residual "
                         "stream for a depth-append hop, else re-prefill "
                         "each session's history")
    ap.add_argument("--hop-operator", default="ligo",
                    choices=["ligo", "lemon", "upcycle"],
                    help="live-hop growth operator: ligo = randomly-"
                         "initialised LiGO to the --grow-to target (the "
                         "production shape; acceptance through the hop is "
                         "whatever the operator earns); lemon = lossless "
                         "zero-pad d_ff doubling of the serving arch "
                         "(--grow-to ignored) — the grown model is bitwise "
                         "identical, so the cache grows in place and a "
                         "resident drafter hits 100%% acceptance; upcycle = "
                         "dense→MoE upcycling to the --grow-to MoE target "
                         "(default: the serving arch's moe_target) — expert-"
                         "replicated FFN + uniform router, function-"
                         "preserving, cache grows in place")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests to serve on the live path "
                         "(default 2x slots)")
    ap.add_argument("--queue-cap", type=int, default=64)
    ap.add_argument("--obs-log", default=None, metavar="FILE",
                    help="stream span/metric events as JSONL to FILE; "
                         "hop flight-recorder dumps land in its directory")
    ap.add_argument("--obs-report", action="store_true",
                    help="print the observability summary at exit "
                         "(p50/p99 decode through-hop, acceptance, pool "
                         "pressure, per-hop-stage walls)")
    ap.add_argument("--obs-profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler start/stop_trace, "
                         "writing the trace to DIR")
    ap.add_argument("--ledger", default=None, metavar="FILE",
                    help="append the compute ledger to FILE: on the serve "
                         "path it carries the hop lifecycle events "
                         "(hop.begin/rollback/complete) and the measured "
                         "decode-step cost pass, alongside any train-side "
                         "records a shared FILE already holds")
    ap.add_argument("--timeline", default=None, metavar="FILE",
                    help="at exit, export the flight-recorder span tree "
                         "(hop grow→cache-grow→swap as async spans; + the "
                         "ledger track when --ledger is set) as Chrome "
                         "trace-event JSON — open in Perfetto")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="N",
                    help="expose the obs registry in Prometheus text "
                         "format at GET /metrics on this port (0 binds an "
                         "ephemeral port; the bound port is printed)")
    ap.add_argument("--grow-to", default=None, metavar="ARCH[,ARCH...]",
                    help="hot-grow the checkpoint to this arch (or '2x' for "
                         "a doubled-depth/1.5x-width same-family target) at "
                         "startup via the cached GrowthPlan executor, then "
                         "serve the grown model. A comma-separated list "
                         "(e.g. '2x,4x') chains hops: the per-hop operators "
                         "compose into one fused apply — no intermediate "
                         "models or checkpoints. Distributed growth: under "
                         "--mesh single|multi (or any ambient mesh) the "
                         "growth runs sharded — in/out shardings follow "
                         "params_pspecs, expanders replicated, the fused "
                         "kernel per-shard under shard_map — so 8B+ targets "
                         "grow in place on the production mesh")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.metrics_port is not None:
        srv = obs.serve_metrics(args.metrics_port)
        print(f"[obs] serving /metrics on http://{srv.server_address[0]}:"
              f"{srv.server_address[1]}/metrics")
    if args.ledger:
        # the serve driver owns no checkpoint cursor: start the serve
        # segment clean (a fresh file, or truncate a stale tail)
        obs.attach_ledger(args.ledger).restore(None)
    if args.obs_log:
        obs.attach_jsonl(args.obs_log)
    try:
        with obs.profile(args.obs_profile):
            res = _serve(args)
    finally:
        if args.obs_report:
            print(obs.report())
        led_path = None
        if args.ledger:
            led = obs.detach_ledger()
            if led is not None:
                led_path = led.path
                print(f"[ledger] compute ledger written to {led_path} "
                      f"({led.n_records} records)")
        if args.timeline:
            led_src = (led_path
                       if led_path and os.path.exists(led_path) else None)
            trace = obs.export_chrome_trace(args.timeline, ledger=led_src)
            print(f"[obs] timeline written to {args.timeline} "
                  f"({len(trace['traceEvents'])} trace events)")
        if args.obs_log:
            path = obs.close_jsonl()
            print(f"[obs] structured log written to {path}")
    if res is not None and not res["hop_completed"]:
        raise SystemExit(f"[serve] the hop to {args.grow_to or '2x'} gave up "
                         f"after {res['hop_attempts']} attempts")
    if res is not None and res["dropped"]:
        raise SystemExit(f"[serve] {res['dropped']} requests dropped")
    return res


def _serve(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))

    with jax.set_mesh(mesh):
        if args.ckpt:
            params = _restore_ckpt(args.ckpt, cfg, mesh)
        else:
            params = init_params(cfg, jax.random.PRNGKey(0))
        if args.live_grow_at is not None:
            return _serve_live(args, cfg, params, mesh)
        if args.grow_to:
            params, cfg = hot_grow(params, cfg, args.grow_to,
                                   smoke=args.smoke)
        prompts = jnp.asarray(
            gen_tokens(0, 0, args.batch, args.prompt_len, cfg.vocab_size)
            [:, :args.prompt_len], jnp.int32)
        max_len = args.prompt_len + args.gen

        batch = {"tokens": prompts}
        if cfg.modality == "vlm":
            P_ = min(cfg.num_patches, args.prompt_len)
            batch["patch_embeds"] = jnp.zeros((args.batch, P_, cfg.d_model),
                                              jnp.float32)
            pos = np.broadcast_to(np.arange(args.prompt_len)[None, :, None],
                                  (args.batch, args.prompt_len, 3)).copy()
            batch["positions"] = jnp.asarray(pos, jnp.int32)

        t0 = time.perf_counter()
        pre = jax.jit(lambda p, b: prefill(p, cfg, b, max_len=max_len))
        logits, state = pre(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        dstep = jax.jit(lambda p, s, b: decode_step(p, cfg, s, b))
        tokens = jnp.argmax(logits, axis=-1)[:, None]
        out = [tokens]
        t0 = time.perf_counter()
        for i in range(args.gen - 1):
            db = {"tokens": tokens}
            if cfg.modality == "vlm":
                pos = jnp.full((args.batch, 1, 3),
                               args.prompt_len + i, jnp.int32)
                db["positions"] = pos
            logits, state = dstep(params, state, db)
            tokens = jnp.argmax(logits, axis=-1)[:, None]
            out.append(tokens)
        jax.block_until_ready(tokens)
        t_decode = time.perf_counter() - t0
        gen = jnp.concatenate(out, axis=1)
        tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
        print(f"[serve] arch={cfg.name} batch={args.batch} "
              f"prompt={args.prompt_len} gen={args.gen}")
        print(f"[serve] prefill {t_prefill*1e3:.1f} ms | decode "
              f"{t_decode*1e3:.1f} ms | {tps:.1f} tok/s")
        print(f"[serve] sample continuation ids: {np.asarray(gen[0][:16])}")


if __name__ == "__main__":
    main()
