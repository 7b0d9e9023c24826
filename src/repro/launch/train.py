"""End-to-end training driver: (optionally) grow from a pretrained smaller
model with LiGO, then train under the production sharding rules with
fault-tolerant supervision.

    # CPU demo (smoke-size arch, host devices):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
        --grow-from half --method ligo --steps 200

    # multi-stage scheduled growth (train→grow→train…, resumable; the
    # smoke schedule ends with a steps="auto" stage, so it runs under the
    # adaptive controller):
    PYTHONPATH=src python -m repro.launch.train \\
        --autogrow examples/trajectory_smoke.json

    # production (TPU pod): same entrypoint with --mesh single|multi.

The grow phase runs *under the same mesh* as training: Θ_small is restored
(or pretrained in-line for the demo), the LiGO operator is trained with pjit
for --ligo-steps, and the materialised Θ_large seeds the main loop.

``--trajectory <cfg.json>`` hands the whole run to
:class:`repro.trajectory.TrajectoryRunner`: an ordered stage schedule whose
checkpoints carry (trajectory hash, stage, stage step), so a killed job
relaunched with the same command resumes mid-trajectory at the correct
stage — AdamW moments ride every hop through the growth operator.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import (TrainConfig, get_config, half_config, smoke_config)
from repro import obs
from repro.core import grow
from repro.data import GlobalBatchLoader
from repro.distributed.sharding import named_shardings, params_pspecs
from repro.distributed.supervisor import Supervisor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import init_params
from repro.optim import adamw_init
from repro.training import make_train_step, pjit_train_step


def build_mesh(kind: str):
    if kind == "host":
        return make_host_mesh()
    return make_production_mesh(multi_pod=(kind == "multi"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--trajectory", default=None, metavar="CFG_JSON",
                    help="run a multi-stage growth trajectory "
                         "(train→grow→train…) from a JSON stage schedule; "
                         "resumable mid-stage via --ckpt-dir")
    ap.add_argument("--autogrow", default=None, metavar="CFG_JSON",
                    help="like --trajectory, with the adaptive growth "
                         "controller enabled: stages may use steps='auto' "
                         "+ a policy block (loss_plateau / rpf_decay / "
                         "probe) and the LiGO phase checkpoints its own "
                         "carry, so a kill mid-hop resumes mid-phase")
    ap.add_argument("--max-steps", type=int, default=None,
                    help="trajectory only: stop (checkpointing) after this "
                         "many global train steps — relaunch resumes")
    ap.add_argument("--fail-at-ligo-step", type=int, default=None,
                    help="chaos testing: raise after the LiGO-phase "
                         "checkpoint at this phase step (the CI kill+resume "
                         "smoke kills mid-hop with it)")
    ap.add_argument("--grow-from", default=None,
                    help="'half' or an arch name: grow instead of cold start")
    ap.add_argument("--method", default="ligo",
                    choices=["ligo", "stackbert", "interpolation", "net2net",
                             "bert2bert", "random"])
    ap.add_argument("--ligo-steps", type=int, default=100)
    ap.add_argument("--pretrain-steps", type=int, default=100,
                    help="demo-only: steps to pretrain the small source")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="host", choices=["host", "single",
                                                       "multi"])
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel residual stream (see §Perf)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-log", default=None, metavar="FILE",
                    help="stream span/metric events as JSONL to FILE "
                         "(ligo.chunk/checkpoint spans, traj.train/grow "
                         "stage walls, autogrow gauges)")
    ap.add_argument("--obs-report", action="store_true",
                    help="print the observability summary at exit")
    ap.add_argument("--obs-profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler start/stop_trace, "
                         "writing the trace to DIR")
    ap.add_argument("--ledger", default=None, metavar="FILE",
                    help="append the durable compute ledger to FILE: one "
                         "JSONL record per train/LiGO step (loss, tokens, "
                         "modelled + measured cumulative FLOPs) plus "
                         "hop/probe events. Requires --trajectory/"
                         "--autogrow — the ledger cursor rides checkpoint "
                         "meta, so a killed run resumes record-identical. "
                         "Feed two ledgers to obs.savings_report for the "
                         "FLOPs-to-target-loss comparison")
    ap.add_argument("--timeline", default=None, metavar="FILE",
                    help="at exit, export the flight-recorder span tree "
                         "(+ the ledger loss/FLOPs track when --ledger is "
                         "set) as Chrome trace-event JSON — open in "
                         "Perfetto or chrome://tracing")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="N",
                    help="expose the obs registry in Prometheus text "
                         "format at GET /metrics on this port (0 binds an "
                         "ephemeral port; the bound port is printed)")
    args = ap.parse_args()
    use_compile_cache()

    if args.ledger and not (args.trajectory or args.autogrow):
        raise SystemExit("--ledger requires --trajectory/--autogrow: the "
                         "trajectory runner owns the cursor-in-checkpoint "
                         "contract that makes the ledger crash-safe")
    if args.metrics_port is not None:
        srv = obs.serve_metrics(args.metrics_port)
        print(f"[obs] serving /metrics on http://{srv.server_address[0]}:"
              f"{srv.server_address[1]}/metrics")
    if args.ledger:
        obs.attach_ledger(args.ledger)
    if args.obs_log:
        obs.attach_jsonl(args.obs_log)
    try:
        with obs.profile(args.obs_profile):
            _train(args)
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


def _train(args):
    if args.trajectory and args.autogrow:
        raise SystemExit("--trajectory and --autogrow are exclusive "
                         "(they name the same schedule file)")
    if args.trajectory or args.autogrow:
        from repro.trajectory import TrajectoryConfig, TrajectoryRunner
        traj = TrajectoryConfig.from_json(args.trajectory or args.autogrow)
        if args.trajectory and traj.has_auto_stages:
            raise SystemExit(
                "the schedule has steps='auto' stages — run it with "
                "--autogrow (the adaptive controller) instead of "
                "--trajectory")
        mesh = build_mesh(args.mesh)
        print(f"[train] trajectory {traj.hash()}: "
              f"{' -> '.join(st.cfg.name for st in traj.stages)} "
              f"({'<=' if traj.has_auto_stages else ''}{traj.total_steps} "
              f"steps) mesh={dict(mesh.shape)}")
        res = TrajectoryRunner(
            traj, ckpt_dir=args.ckpt_dir, mesh=mesh,
            ligo_fail_at=args.fail_at_ligo_step).run(
                max_steps=args.max_steps)
        for d in res["decisions"]:
            print(f"[train] autogrow decision: {d}")
        print(f"[train] trajectory {res['status']}: stage "
              f"{res['stage'] + 1}/{len(traj.stages)} ({res['cfg'].name}) "
              f"global_step={res['global_step']} "
              f"final_loss={res['history'][-1][2]:.4f}"
              if res["history"] else
              f"[train] trajectory {res['status']} (no steps run)")
        return

    if not args.arch:
        raise SystemExit("--arch is required (or pass --trajectory)")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.objective != "clm":
        raise SystemExit("train driver demo supports CLM archs; "
                         "MLM/vision run through benchmarks + tests")

    mesh = build_mesh(args.mesh)
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} "
          f"params={cfg.param_count()/1e6:.1f}M")
    tcfg = TrainConfig(steps=args.steps, warmup_steps=max(args.steps // 20, 5),
                       lr=args.lr, seq_len=args.seq, global_batch=args.batch,
                       checkpoint_every=args.checkpoint_every)

    model_sz = mesh.shape.get("model", 1)
    dp_sz = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    act_spec = P("data", "model", None) if args.seq_shard else None

    with jax.set_mesh(mesh):
        # ---- source model ------------------------------------------------
        if args.grow_from:
            small_cfg = (half_config(cfg) if args.grow_from == "half"
                         else smoke_config(get_config(args.grow_from))
                         if args.smoke else get_config(args.grow_from))
            print(f"[train] pretraining source {small_cfg.name} "
                  f"({small_cfg.param_count()/1e6:.1f}M) for "
                  f"{args.pretrain_steps} steps")
            sp = init_params(small_cfg, jax.random.PRNGKey(args.seed))
            # source weights live under the same sharding rules as the big
            # model's, so the grow phase (apply_ligo picks up the ambient
            # mesh -> sharded GrowthPlan executor) starts from mesh-resident
            # leaves and the materialised tree lands pre-sharded for the
            # main loop.
            sp = jax.tree.map(jax.device_put, sp, named_shardings(
                params_pspecs(sp, model_size=model_sz, dp_size=dp_sz), mesh))
            s_opt = adamw_init(sp)
            s_step = jax.jit(make_train_step(small_cfg, tcfg))
            s_loader = GlobalBatchLoader(small_cfg, mesh, args.batch,
                                         args.seq, seed=args.seed)
            for i in range(args.pretrain_steps):
                sp, s_opt, m = s_step(sp, s_opt, s_loader.batch_at(i),
                                      jnp.asarray(i))
            print(f"[train] source loss {float(m['total']):.4f}")
            g_loader = GlobalBatchLoader(small_cfg, mesh, args.batch,
                                         args.seq, seed=args.seed + 1)
            params, info = grow(
                sp, small_cfg, cfg, method=args.method,
                key=jax.random.PRNGKey(args.seed + 2),
                data_it=iter(g_loader), ligo_steps=args.ligo_steps)
            if "ligo_losses" in info:
                ll = info["ligo_losses"]
                print(f"[train] LiGO phase: {ll[0]:.4f} -> {ll[-1]:.4f} "
                      f"({len(ll)} steps)")
        else:
            params = init_params(cfg, jax.random.PRNGKey(args.seed))

        # ---- sharded training loop ---------------------------------------
        step_fn = make_train_step(cfg, tcfg, act_spec=act_spec)
        loader = GlobalBatchLoader(cfg, mesh, args.batch, args.seq,
                                   seed=args.seed + 10)
        jstep, psh, osh = pjit_train_step(step_fn, params,
                                          loader.batch_at(0), mesh)
        params = jax.tree.map(jax.device_put, params, psh)
        opt = adamw_init(params)

        # checkpoints carry the run's identity; an elastic restart consumes
        # the whole meta dict — refusing a checkpoint from a different arch
        # (e.g. a reused --ckpt-dir) instead of crashing on shapes, and
        # landing on the exact recorded step. The meta peek must happen
        # BEFORE the restore: restore_latest unflattens into this arch's
        # template and would die on the shape/key mismatch first.
        run_meta = {"arch": cfg.name, "config": cfg.config_hash()}
        sup = Supervisor(ckpt_dir=args.ckpt_dir,
                         checkpoint_every=args.checkpoint_every)
        meta = sup.mgr.latest_meta()
        if meta is not None:
            if "trajectory" in meta:
                raise SystemExit(
                    f"--ckpt-dir holds a trajectory checkpoint (stage "
                    f"{meta.get('stage')}); resume it with --trajectory / "
                    "--autogrow")
            if meta.get("config", cfg.config_hash()) != cfg.config_hash():
                raise SystemExit(
                    f"--ckpt-dir holds a checkpoint of "
                    f"{meta.get('arch', '?')} ({meta.get('config')}), not "
                    f"{cfg.name} ({cfg.config_hash()}) — refusing to resume")
        restored = sup.resume({"params": params, "opt": opt},
                              shardings={"params": psh, "opt": osh})
        start = 0
        if restored is not None:
            state, meta = restored
            params, opt = state["params"], state["opt"]
            start = int(meta.get("step", 0))
            print(f"[train] resumed {meta.get('arch', cfg.name)} "
                  f"from step {start}")

        def on_metrics(step, m):
            if step % 20 == 0:
                print(f"[train] step {step:5d} loss {float(m['total']):.4f} "
                      f"lr {float(m['lr']):.2e} gnorm "
                      f"{float(m['grad_norm']):.2f}", flush=True)

        state = sup.run({"params": params, "opt": opt},
                        lambda p, o, b, s: jstep(p, o, b, jnp.asarray(s)),
                        loader.batch_at, start_step=start, steps=args.steps,
                        state_shardings={"params": psh, "opt": osh},
                        on_metrics=on_metrics, meta=run_meta)
        final = sup.history[-1][1] if sup.history else float("nan")
        print(f"[train] done: steps={args.steps} final_loss={final:.4f} "
              f"stragglers={len(sup.watchdog.flagged)} "
              f"restarts={sup.restarts}")


if __name__ == "__main__":
    main()
