"""TrajectoryRunner: execute train→grow→train… as one resumable job.

One runner call drives a whole :class:`~repro.trajectory.config.
TrajectoryConfig`: pretrain stage 0, grow into stage 1 (operator learned or
built per the stage's :class:`GrowthSpec`, parameters AND AdamW moments
carried through it), train stage 1, grow again, … Every leg runs under the
runner's mesh (or the ambient one): growth goes through the sharded
GrowthPlan executor, training through a pjit'd train step with
``params_pspecs`` shardings, so the same code covers the 1-device CPU smoke
and a production pod.

Adaptive scheduling (:mod:`repro.autogrow`): a stage with ``steps="auto"``
ends when its growth policy fires on the stage's telemetry stream (loss EMA
/ return-per-FLOP over a ring buffer) instead of at a fixed count. The
telemetry tail rides every checkpoint's meta, so a resumed stage replays the
identical decision sequence. A ``probe`` policy additionally short-trains the
candidate growth operators at the hop and commits the winner (LAG-style).

Resumability: every checkpoint the runner writes carries
``{trajectory, stage, stage_step, global_step, arch, config}`` in its meta.
A fresh runner pointed at the same directory peeks the meta first
(:meth:`CheckpointManager.latest_meta` — arrays untouched), validates the
trajectory hash, rebuilds the *stage-correct* template and mesh shardings,
and restores into them — so a job killed mid-stage resumes at the exact
(stage, step) it died on, on any device count. A post-growth snapshot is
written at every stage entry, so a completed (possibly expensive) growth is
never redone on restart. The LiGO phase *inside* a hop is elastic too: its
``(ligo, momentum, step)`` scan carry is checkpointed under
``<ckpt_dir>/ligo_phase`` between chunks (:func:`repro.core.grow.
train_ligo`), so a kill during a long operator-learning leg resumes
mid-phase, never from the stage boundary.

Consecutive zero-step stages whose hops need no intermediate model
(classical operators / init-only LiGO) are executed as ONE composed fused
hop — the skip-stage path: parameters and first moments ride the
analytically composed operator, second moments follow the GQA rule
(:func:`repro.optim.grow_adamw_state_chain` — per hop under grouped
``gamma``, composed otherwise).

``run(max_steps=N)`` stops after N global train steps (checkpointing first)
— the deterministic "kill" used by the tests and the CI smoke; calling
``run()`` again on a new runner finishes the job.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.autogrow import Telemetry, make_policy, probe_methods
from repro.checkpoint import CheckpointManager
from repro.configs.base import TrainConfig
from repro.core import apply_ligo, compose_chain, grow
from repro.data import GlobalBatchLoader
from repro.models.model import init_params
from repro.optim import adamw_init, grow_adamw_state_chain
from repro.roofline import train_flops_per_step
from repro.trajectory.config import TrajectoryConfig
from repro.training import (make_train_step, pjit_train_step,
                            train_state_shardings)

LIGO_PHASE_DIR = "ligo_phase"


class TrajectoryRunner:
    def __init__(self, traj: TrajectoryConfig, *, ckpt_dir: str,
                 mesh=None, keep: int = 3, verbose: bool = True,
                 ligo_fail_at: Optional[int] = None, ledger=None):
        self.traj = traj
        self.mgr = CheckpointManager(ckpt_dir, keep=keep)
        self.mesh = mesh
        self.verbose = verbose
        self.resumed_at: Optional[Tuple[int, int]] = None
        # chaos knob: inject a failure after the LiGO-phase checkpoint at
        # this phase step (threaded into train_ligo; tests + CI smoke)
        self.ligo_fail_at = ligo_fail_at
        self.decisions: List[Dict[str, Any]] = []
        # stage index -> the (composed) operator that grew into it in this
        # process; callers inspect or re-apply the learned operators
        self.operators: Dict[int, Dict] = {}
        self._tele_restore: Optional[Dict] = None
        # the compute ledger (explicit, or whatever --ledger attached):
        # its cursor rides every checkpoint meta like the telemetry ring,
        # and its per-step FLOPs columns come from the measured-cost pass
        self.ledger = ledger if ledger is not None else obs.active_ledger()

    # ------------------------------------------------------------------
    def _log(self, msg: str) -> None:
        if self.verbose:
            print(f"[traj] {msg}", flush=True)

    def _meta(self, stage: int, stage_step: int, global_step: int,
              tele: Optional[Telemetry] = None) -> Dict:
        cfg = self.traj.stages[stage].cfg
        meta = {"trajectory": self.traj.hash(), "stage": stage,
                "stage_step": stage_step, "global_step": global_step,
                "arch": cfg.name, "config": cfg.config_hash()}
        if tele is not None:
            # the controller's signal state rides the checkpoint, so a
            # resumed auto stage replays the same growth decision
            meta["autogrow"] = tele.snapshot()
        if self.ledger is not None:
            # ledger cursor: snapshot() fsyncs the file first, so every
            # record up to this offset is durable before the checkpoint
            # carrying the cursor lands — restore truncates back to it
            meta["ledger"] = self.ledger.snapshot()
        return meta

    def _template(self, stage: int):
        cfg = self.traj.stages[stage].cfg
        params_t = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(self.traj.seed)))
        opt_t = jax.eval_shape(adamw_init, params_t)
        return {"params": params_t, "opt": opt_t}

    def _shardings(self, template_params):
        if self.mesh is None:
            return None, None
        return train_state_shardings(template_params, self.mesh)

    @property
    def _phase_dir(self) -> str:
        return os.path.join(self.mgr.dir, LIGO_PHASE_DIR)

    # ------------------------------------------------------------------
    def _restore_or_init(self):
        meta = self.mgr.latest_meta()
        if meta is None:
            if self.ledger is not None:
                self.ledger.restore(None)      # fresh run: empty ledger
            cfg0 = self.traj.stages[0].cfg
            params = init_params(cfg0, jax.random.PRNGKey(self.traj.seed))
            return 0, 0, 0, params, adamw_init(params)
        if meta.get("trajectory") != self.traj.hash():
            raise ValueError(
                f"checkpoint dir {self.mgr.dir!r} belongs to trajectory "
                f"{meta.get('trajectory')!r}, not {self.traj.hash()!r} — "
                "refusing to resume a different schedule")
        stage, k = int(meta["stage"]), int(meta["stage_step"])
        g = int(meta["global_step"])
        tmpl = self._template(stage)
        psh, osh = self._shardings(tmpl["params"])
        shardings = (None if psh is None
                     else {"params": psh, "opt": osh})
        try:
            state, _ = self.mgr.restore(self.mgr.latest_step(), tmpl,
                                        shardings)
        except KeyError as e:
            if "opt" in str(e):
                raise ValueError(
                    f"checkpoint in {self.mgr.dir!r} has no optimizer "
                    "state (it predates grow_state / was written by an "
                    "older trainer) — a growth trajectory cannot resume "
                    "from it: the AdamW moments must ride every hop. "
                    "Delete the directory to restart, or re-checkpoint "
                    f"with the current trainer. (missing leaf: {e})"
                ) from e
            raise
        self._tele_restore = meta.get("autogrow")
        if self.ledger is not None:
            # truncate the ledger back to this checkpoint's cursor; the
            # re-executed steps re-append identical records (the runner is
            # deterministic), including the tail a mid-LiGO kill left —
            # train_ligo replays its phase-checkpoint losses into the
            # ledger on resume
            self.ledger.restore(meta.get("ledger"))
        self.resumed_at = (stage, k)
        self._log(f"resumed trajectory {self.traj.hash()} at stage {stage} "
                  f"step {k} ({meta['arch']})")
        return stage, k, g, state["params"], state["opt"]

    # ------------------------------------------------------------------
    def _stage_step_fn(self, stage: int, params):
        """(jitted step, loader, shardings, measurement) for one stage's
        train leg. The measurement (None unless a ledger is active) is
        the compile-time measured-cost pass over the same jitted program:
        FLOPs read back from XLA, per train step."""
        st = self.traj.stages[stage]
        tcfg = TrainConfig(steps=st.budget,
                           warmup_steps=max(st.budget // 10, 1),
                           lr=self.traj.lr, seq_len=self.traj.seq,
                           global_batch=self.traj.batch)
        step_fn = make_train_step(st.cfg, tcfg)
        loader = GlobalBatchLoader(st.cfg, self.mesh, self.traj.batch,
                                   self.traj.seq,
                                   seed=self.traj.seed + 101 * stage)
        if self.mesh is None:
            jstep, psh, osh = jax.jit(step_fn), None, None
        else:
            jstep, psh, osh = pjit_train_step(step_fn, params,
                                              loader.batch_at(0), self.mesh)
        meas = None
        if self.ledger is not None:
            from repro.obs import costs
            meas = costs.measure_jitted(
                f"train_step[{st.cfg.name}]", jstep, params,
                jax.eval_shape(adamw_init, params), loader.batch_at(0),
                jnp.asarray(0),
                modelled_flops=train_flops_per_step(
                    st.cfg, self.traj.batch, self.traj.seq),
                n_devices=1 if self.mesh is None else self.mesh.size)
        return jstep, loader, psh, osh, meas

    def _stage_controller(self, stage: int):
        """(policy, telemetry) for an auto stage; (None, None) for static
        stages — a static budget needs no per-step decision."""
        st = self.traj.stages[stage]
        if not st.auto:
            return None, None
        pol = make_policy(st.policy)
        fps = train_flops_per_step(st.cfg, self.traj.batch, self.traj.seq)
        tokens = float(self.traj.batch * self.traj.seq)
        if self._tele_restore is not None:
            tele = Telemetry.restore(self._tele_restore,
                                     flops_per_step=fps,
                                     tokens_per_step=tokens)
            self._tele_restore = None
        else:
            tele = pol.telemetry(flops_per_step=fps, tokens_per_step=tokens)
        return pol, tele

    # ------------------------------------------------------------------
    def _chain_end(self, stage: int) -> int:
        """Last stage of the composable hop run starting at ``stage``.

        Extends through following zero-step stages whose entry operators
        need no intermediate model (any classical method, or LiGO with a
        zero training budget) and exist at all (not ``random``) — those
        hops collapse into ONE composed fused apply."""
        stages = self.traj.stages
        if stages[stage].growth.method == "random":
            return stage                    # no operator, nothing composes
        last = stage
        while last < len(stages) - 1 and stages[last].budget == 0:
            g = stages[last + 1].growth
            if g.method == "random" or (g.method == "ligo"
                                        and g.ligo_steps > 0):
                break
            last += 1
        return last

    def _hop_operator(self, stage: int, params, *, method=None):
        """Build (and for LiGO, train) the operator entering ``stage`` —
        elastic: the LiGO phase checkpoints its carry under
        ``<ckpt_dir>/ligo_phase`` and resumes mid-phase on restart."""
        st = self.traj.stages[stage]
        gs = st.growth
        if method is not None and method != gs.method:
            gs = dataclasses.replace(gs, method=method)
        prev_cfg = self.traj.stages[stage - 1].cfg
        needs_data = gs.method == "ligo" and gs.ligo_steps > 0
        data_it = None
        ligo_ckpt = None
        if needs_data:
            g_loader = GlobalBatchLoader(prev_cfg, self.mesh,
                                         self.traj.batch, self.traj.seq,
                                         seed=self.traj.seed + 101 * stage
                                         + 53)
            data_it = iter(g_loader)
            ligo_ckpt = CheckpointManager(self._phase_dir, keep=2)
        _, info = grow(
            params, prev_cfg, st.cfg, method=gs.method,
            key=jax.random.PRNGKey(self.traj.seed + 7 * stage),
            data_it=data_it, ligo_steps=gs.ligo_steps,
            ligo_lr=gs.ligo_lr, ligo_momentum=gs.ligo_momentum,
            apply=False, ligo_ckpt=ligo_ckpt,
            ligo_meta={"trajectory": self.traj.hash(), "stage": stage},
            ligo_scan_chunk=gs.ligo_scan_chunk,
            ligo_fail_at=self.ligo_fail_at,
            ligo_ledger=self.ledger,
            ligo_ledger_ctx=None if self.ledger is None else {
                "stage": stage,
                "n_devices": 1 if self.mesh is None else self.mesh.size})
        return info["operator"], gs

    def _grow_into(self, stage: int, params, opt, *, method=None):
        """Hop stage-1 → stage (possibly collapsing a run of zero-step
        stages into one composed hop): params and AdamW moments through the
        operator(s), fresh moments otherwise. Returns
        ``(landed_stage, params, opt, grow_ms)``."""
        stages = self.traj.stages
        gs0 = stages[stage].growth
        t0 = time.perf_counter()
        if (method or gs0.method) == "random":
            st = stages[stage]
            params, info = grow(
                params, stages[stage - 1].cfg, st.cfg, method="random",
                key=jax.random.PRNGKey(self.traj.seed + 7 * stage),
                opt_state=opt)
            opt = info["opt_state"]
            jax.block_until_ready(jax.tree.leaves(params)[0])
            grow_ms = (time.perf_counter() - t0) * 1e3
            self._log(f"stage {stage}: fresh init of {st.cfg.name} "
                      f"(method=random) in {grow_ms:.0f} ms")
            return stage, params, opt, grow_ms

        last = self._chain_end(stage)
        cfg_chain = [stages[j].cfg for j in range(stage - 1, last + 1)]
        ops_chain, specs = [], []
        for idx, j in enumerate(range(stage, last + 1)):
            op, gs = self._hop_operator(j, params,
                                        method=method if idx == 0 else None)
            ops_chain.append(op)
            specs.append(gs)
        composed = (ops_chain[0] if len(ops_chain) == 1
                    else compose_chain(ops_chain, cfg_chain))
        self.operators[last] = composed
        params = apply_ligo(composed, params, cfg_chain[0], cfg_chain[-1],
                            mesh=self.mesh)
        carry = all(gs.grow_optimizer for gs in specs)
        if carry:
            # the chain rule: m through the composed operator, v per hop
            # when any hop's gamma group-averages (GQA) — LEMON-exact
            opt = grow_adamw_state_chain(opt, ops_chain, cfg_chain,
                                         mesh=self.mesh)
        else:
            opt = adamw_init(params)
        jax.block_until_ready(jax.tree.leaves(params)[0])
        grow_ms = (time.perf_counter() - t0) * 1e3
        hops = " -> ".join(c.name for c in cfg_chain)
        self._log(f"grew {hops} "
                  f"({'composed, ' if len(ops_chain) > 1 else ''}"
                  f"method={'+'.join(gs.method for gs in specs)}, "
                  f"opt moments {'carried' if carry else 'reset'}) "
                  f"in {grow_ms:.0f} ms")
        return last, params, opt, grow_ms

    # ------------------------------------------------------------------
    def run(self, *, max_steps: Optional[int] = None,
            on_metrics=None) -> Dict[str, Any]:
        """Drive the trajectory to completion (or to ``max_steps`` global
        train steps). Returns the final state + bookkeeping; ``status`` is
        ``"done"`` or ``"paused"``."""
        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else nullcontext())
        with ctx:
            return self._run(max_steps, on_metrics)

    def _run(self, max_steps, on_metrics) -> Dict[str, Any]:
        stages = self.traj.stages
        stage, k, global_step, params, opt = self._restore_or_init()
        history: list = []
        timings: Dict[int, Dict[str, float]] = {}

        def timing(s: int) -> Dict[str, float]:
            return timings.setdefault(s, {"train_ms": 0.0, "grow_ms": 0.0})

        # per-stage walls also land in the obs registry (spans "traj.train"
        # / "traj.grow" carry the same walls in the flight recorder)
        h_train = obs.histogram("traj.stage.train_ms")
        h_grow = obs.histogram("traj.stage.grow_ms")

        # the identity of the last checkpoint written (or restored from),
        # so stage-end/done saves don't rewrite the step the periodic
        # in-loop save just flushed
        last_saved = [self.resumed_at + (global_step,)
                      if self.resumed_at is not None else None]

        def save(s: int, kk: int, g: int, *, tele=None,
                 block: bool = False) -> None:
            self.mgr.save(g, {"params": params, "opt": opt},
                          self._meta(s, kk, g, tele), block=block)
            last_saved[0] = (s, kk, g)

        def save_once(s: int, kk: int, g: int, *, tele=None,
                      block: bool = False) -> None:
            if last_saved[0] != (s, kk, g):
                save(s, kk, g, tele=tele, block=block)
            elif block:
                self.mgr.wait()

        def result(status: str) -> Dict[str, Any]:
            self.mgr.wait()
            return {"params": params, "opt": opt,
                    "cfg": stages[stage].cfg, "stage": stage,
                    "stage_step": k, "global_step": global_step,
                    "history": history, "status": status,
                    "resumed_at": self.resumed_at, "timings": timings,
                    "decisions": self.decisions,
                    "operators": self.operators}

        while True:
            st = stages[stage]
            pol, tele = self._stage_controller(stage)
            if k < st.budget:
                self._log(f"stage {stage + 1}/{len(stages)}: {st.cfg.name} "
                          f"({st.cfg.param_count() / 1e6:.1f}M) "
                          f"steps [{k}, "
                          f"{'auto<=' if st.auto else ''}{st.budget})")
                t_train = time.perf_counter()
                with obs.span("traj.train", stage=stage,
                              arch=st.cfg.name, start=k):
                    jstep, loader, psh, osh, meas = self._stage_step_fn(
                        stage, params)
                    fps_model = tokens_step = meas_fps = None
                    if self.ledger is not None:
                        fps_model = train_flops_per_step(
                            st.cfg, self.traj.batch, self.traj.seq)
                        tokens_step = float(self.traj.batch * self.traj.seq)
                        meas_fps = (meas or {}).get("flops_per_unit")
                        if tele is not None and meas_fps is not None:
                            # the controller's cum-FLOPs axis follows the
                            # measured number; deterministic across resume
                            # because the resumed process re-measures the
                            # same program before its first record
                            tele.set_flops_per_step(meas_fps)
                    if psh is not None:
                        params = jax.tree.map(jax.device_put, params, psh)
                        opt = jax.tree.map(jax.device_put, opt, osh)
                    while k < st.budget:
                        if pol is not None and pol.should_grow(k, tele):
                            self.decisions.append(
                                {"stage": stage, "stage_step": k,
                                 "global_step": global_step,
                                 "kind": st.policy.kind,
                                 "why": pol.why(k, tele)})
                            self._log(f"stage {stage + 1} policy fired at "
                                      f"step {k}: {pol.why(k, tele)}")
                            break
                        if max_steps is not None and global_step >= max_steps:
                            dt = (time.perf_counter() - t_train) * 1e3
                            timing(stage)["train_ms"] += dt
                            h_train.observe(dt)
                            save_once(stage, k, global_step, tele=tele,
                                      block=True)
                            self._log(f"paused at global step {global_step} "
                                      f"(stage {stage} step {k})")
                            return result("paused")
                        batch = loader.batch_at(k)
                        t_step = time.perf_counter()
                        params, opt, m = jstep(params, opt, batch,
                                               jnp.asarray(k))
                        k += 1
                        global_step += 1
                        loss = float(m["total"])      # host sync point
                        history.append((global_step, stage, loss))
                        if self.ledger is not None:
                            self.ledger.record_step(
                                stage=stage, arch=st.cfg.name,
                                step=global_step, loss=loss,
                                tokens=tokens_step,
                                wall_ms=(time.perf_counter() - t_step) * 1e3,
                                flops_modelled=fps_model,
                                flops_measured=meas_fps)
                        if tele is not None:
                            tele.record(global_step, loss)
                        if on_metrics is not None:
                            on_metrics(global_step, stage, m)
                        if k % self.traj.checkpoint_every == 0:
                            save(stage, k, global_step, tele=tele)
                    dt = (time.perf_counter() - t_train) * 1e3
                    timing(stage)["train_ms"] += dt
                    h_train.observe(dt)
                # the stage-end save: a kill during the following hop
                # resumes here (the hop's own LiGO-phase checkpoints carry
                # the intra-hop progress)
                save_once(stage, k, global_step, tele=tele)
                # history holds only THIS process's steps: a resumed stage
                # whose policy fires immediately has run none of them
                self._log(f"stage {stage + 1} done ({k} steps)"
                          + (f": loss {history[-1][2]:.4f}" if history
                             else ""))
            if stage + 1 == len(stages):
                save_once(stage, k, global_step, block=True)
                return result("done")
            method = None
            nxt = stages[stage + 1]
            if (st.auto and st.policy.kind == "probe"
                    and nxt.growth.method != "random"):
                method, scores = probe_methods(
                    params, opt, st.cfg, nxt.cfg, st.policy,
                    lr=self.traj.lr, batch=self.traj.batch,
                    seq=self.traj.seq,
                    seed=self.traj.seed + 1009 * (stage + 1),
                    verbose=self.verbose)
                self.decisions.append(
                    {"stage": stage, "stage_step": k,
                     "global_step": global_step, "kind": "probe",
                     "picked": method, "scores": scores})
                if self.ledger is not None:
                    self.ledger.record_event(
                        "probe", stage=stage, step=global_step,
                        picked=method,
                        scores={m: float(s) for m, s in sorted(
                            scores.items())})
                self._log(f"probe picked method={method} "
                          f"({', '.join(f'{m}={s:.4f}' for m, s in sorted(scores.items()))})")
            if self.ledger is not None:
                self.ledger.record_event(
                    "hop.begin", stage=stage + 1, step=global_step,
                    src=st.cfg.name, dst=nxt.cfg.name,
                    method=method or nxt.growth.method)
            with obs.span("traj.grow", stage=stage + 1,
                          src=st.cfg.name, dst=nxt.cfg.name):
                stage, params, opt, grow_ms = self._grow_into(
                    stage + 1, params, opt, method=method)
            if self.ledger is not None:
                # deterministic attrs only — the wall lives in the span
                self.ledger.record_event(
                    "hop.complete", stage=stage, step=global_step,
                    src=st.cfg.name, dst=stages[stage].cfg.name)
            timing(stage)["grow_ms"] = grow_ms
            h_grow.observe(grow_ms)
            k = 0
            # post-growth snapshot (same global step, new stage meta):
            # replaces the stage-end save, so a restart never redoes the hop
            save(stage, 0, global_step, block=True)
            # the hop (and its elastic LiGO phase) is durably snapshotted
            # above — the phase carry has served its purpose
            shutil.rmtree(self._phase_dir, ignore_errors=True)


def run_trajectory(traj: TrajectoryConfig, *, ckpt_dir: str, mesh=None,
                   max_steps: Optional[int] = None,
                   verbose: bool = True) -> Dict[str, Any]:
    """One-shot convenience wrapper around :class:`TrajectoryRunner`."""
    return TrajectoryRunner(traj, ckpt_dir=ckpt_dir, mesh=mesh,
                            verbose=verbose).run(max_steps=max_steps)
