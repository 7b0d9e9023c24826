#!/usr/bin/env python3
"""Run the system's main path once on a TPU, at published widths.

    python3 chip_smoke.py              # one chip: train -> LiGO hop -> serve
    python3 chip_smoke.py --chips 4    # four chips: the sharded 8B growth only

Everything runs in this one process: a chip belongs to one process at a
time, so the phases call the entry points' functions in-process and start
no other process that touches JAX. Weights are random, made from fixed
seeds.

One chip:

1. Device check: the first device must be a TPU. There is no CPU fallback.
2. Train through a LiGO hop: the paper's BERT-small -> BERT-base pair at
   published widths and bf16, through ``TrajectoryRunner`` (the path
   ``launch/train.py --trajectory`` takes) on the schedule in
   ``examples/chip_smoke_bert.json``: stage-0 steps of BERT-small, a LiGO
   phase, then BERT-base steps at batch 8 and sequence 512. Prints each
   phase's losses and wall time, the fused-kernel launch counts, how many
   plan groups take the fused kernels, and checks the fused apply and its
   gradient against the einsum route on the trained operator.
3. Serve through a live hop: ``launch/serve.py --arch gpt2-base
   --live-grow-at 8 --grow-to gpt2-medium --requests 16``. Every request
   must finish, none may drop, and the hop must land on its first attempt.

Four chips (``--chips 4``), and nothing else: grow ``half_config(llama3-8b)``
into ``llama3-8b`` at bf16 with the sharded ``GrowthPlan`` executor on a
2x2 ("data", "model") mesh, print each device's bytes, and compare a few
grown leaves with the einsum reference computed leaf by leaf on one device.

The persistent compilation cache is on (``launch/compile_cache.py``); the
compile seconds printed at the end drop on a second run. The last line of
stdout is ``{"ok": true, "device": {...}}``. A failed check exits non-zero
without printing it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SCHEDULE = os.path.join(ROOT, "examples", "chip_smoke_bert.json")
# Checkpoints and the run ledger of the train phase (listed in .gitignore).
RUN_DIR = os.path.join(ROOT, ".chip_smoke")
# Per-leaf bound on max |got - want| / max |want| between bf16 results that
# differ only in accumulation order and rounding points.
BF16_TOL = 2e-2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds the backend spent compiling, and persistent-cache hits and
    misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.seconds:.1f} s compiling, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def rel_err(got, want) -> float:
    """max |got - want| / max |want|, in float32, on ``want``'s device."""
    import jax
    import jax.numpy as jnp
    want = jnp.asarray(want, jnp.float32)
    got = jax.device_put(got, list(want.devices())[0]).astype(jnp.float32)
    scale = jnp.max(jnp.abs(want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(scale, 1e-30))


def fused_vs_einsum(cfg1, cfg2, op, seed: int = 0) -> dict:
    """Grow fresh ``cfg1`` weights with ``op`` through the fused kernels and
    through the einsum route, and compare the grown trees and the operator
    gradients of a sum-of-squares loss leaf by leaf."""
    import jax
    import jax.numpy as jnp
    from repro.core import plan_for
    from repro.models import init_params

    sp = init_params(cfg1, jax.random.PRNGKey(seed))
    plan = plan_for(cfg1, cfg2, sp)

    def grown(use_kernel):
        return plan.executor(use_kernel=use_kernel)(op, sp)

    def grads(use_kernel):
        def loss(o, small):
            big = plan.apply(o, small, use_kernel=use_kernel)
            return sum(jnp.mean(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree.leaves(big))
        return jax.jit(jax.grad(loss))(op, sp)

    apply_err = max(rel_err(a, b) for a, b in zip(
        jax.tree.leaves(grown(True)), jax.tree.leaves(grown(False))))
    grad_err = max(rel_err(a, b) for a, b in zip(
        jax.tree.leaves(grads(True)), jax.tree.leaves(grads(False))))
    k, n = plan.kernel_groups()
    return {"fused_groups": k, "groups": n, "apply_rel_err": apply_err,
            "grad_rel_err": grad_err}


def train_phase(traj, run_dir: str) -> dict:
    """Phase 2: the trajectory ``traj`` (train -> LiGO hop -> train), plus
    the fused vs einsum comparison on the operator the LiGO phase learned."""
    from repro import obs
    from repro.kernels import LAUNCH_COUNTS
    from repro.launch.mesh import make_host_mesh
    from repro.obs.ledger import RunLedger, read_ledger
    from repro.trajectory import TrajectoryRunner

    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ledger = RunLedger(os.path.join(run_dir, "ledger.jsonl"))
    names = " -> ".join(st.cfg.name for st in traj.stages)
    log(f"train: {names}, batch {traj.batch}, seq {traj.seq}, "
        f"dtype {traj.stages[-1].cfg.dtype}")
    LAUNCH_COUNTS.clear()
    t0 = time.perf_counter()
    res = TrajectoryRunner(traj, ckpt_dir=os.path.join(run_dir, "ckpt"),
                           mesh=make_host_mesh(), ledger=ledger).run()
    wall = time.perf_counter() - t0
    shutil.rmtree(os.path.join(run_dir, "ckpt"), ignore_errors=True)
    check(res["status"] == "done", f"trajectory ended {res['status']}")

    steps = [r for r in read_ledger(ledger.path) if r.get("type") == "step"]
    out = {"wall_s": wall, "phases": []}
    for stage, st in enumerate(traj.stages):
        legs = ([("ligo", stage)] if stage else []) + [("train", stage)]
        for phase, s in legs:
            recs = [r for r in steps
                    if r["phase"] == phase and r["stage"] == s]
            losses = [r["loss"] for r in recs]
            check(losses, f"no {phase} steps recorded for stage {s}")
            check(all(math.isfinite(x) for x in losses),
                  f"non-finite {phase} loss in stage {s}: {losses}")
            t = res["timings"].get(s, {})
            ms = t.get("grow_ms" if phase == "ligo" else "train_ms", 0.0)
            arch = recs[0]["arch"]
            log(f"train: stage {s} {phase} {arch}: {len(losses)} steps, "
                f"losses {[round(x, 4) for x in losses]}, wall {ms:.0f} ms"
                + (" (LiGO phase + hop apply)" if phase == "ligo" else ""))
            out["phases"].append({"stage": s, "phase": phase, "arch": arch,
                                  "losses": losses, "wall_ms": ms})

    launches = dict(LAUNCH_COUNTS.items())
    log(f"train: kernels.launches {launches} (trace-time counts)")
    check(launches.get("fwd", 0) > 0 and launches.get("bwd", 0) > 0,
          f"the fused kernels never ran in the LiGO phase: {launches}")
    failures = dict(obs.counter_group("ledger.measure.failures").items())
    check(not failures, f"measured-cost pass failed for {failures}")
    out["launches"] = launches

    cmp = fused_vs_einsum(traj.stages[0].cfg, traj.stages[1].cfg,
                          res["operators"][1], seed=traj.seed)
    log(f"train: {cmp['fused_groups']}/{cmp['groups']} plan groups on the "
        f"fused kernels; fused vs einsum on the trained operator: apply "
        f"max rel err {cmp['apply_rel_err']:.3e}, operator-gradient max "
        f"rel err {cmp['grad_rel_err']:.3e} (bound {BF16_TOL})")
    check(cmp["fused_groups"] > 0, "no plan group takes the fused kernels")
    check(cmp["apply_rel_err"] <= BF16_TOL,
          f"fused apply differs from einsum by {cmp['apply_rel_err']:.3e}")
    check(cmp["grad_rel_err"] <= BF16_TOL,
          f"fused gradient differs from einsum by {cmp['grad_rel_err']:.3e}")
    out.update(cmp)
    return out


def serve_phase(argv) -> dict:
    """Phase 3: ``launch/serve.py`` with a live hop, in this process."""
    from repro.launch import serve
    log(f"serve: {' '.join(argv)}")
    try:
        res = serve.main(argv)
    except SystemExit as e:
        raise SmokeFailure(f"serve exited: {e}") from None
    log(f"serve: {res['done']}/{res['requests']} done, {res['dropped']} "
        f"dropped, {res['rejected']} rejected, hop "
        f"{'complete' if res['hop_completed'] else 'FAILED'} on attempt "
        f"{res['hop_attempts']} (cache: {res['cache_path']}), now serving "
        f"{res['arch']}, {res['tokens']} tokens in {res['wall_s']:.2f} s")
    check(res["done"] == res["requests"],
          f"{res['done']} of {res['requests']} requests finished")
    check(res["dropped"] == 0 and res["rejected"] == 0,
          f"{res['dropped']} dropped, {res['rejected']} rejected")
    check(res["hop_completed"] and res["hop_attempts"] == 1,
          f"hop completed={res['hop_completed']} after "
          f"{res['hop_attempts']} attempts")
    return res


def sharded_phase(cfg1, cfg2, mesh, *, ref_leaves=("wq", "wk", "wo"),
                  seed: int = 0) -> dict:
    """Phase 4: grow ``cfg1`` into ``cfg2`` with the sharded GrowthPlan
    executor on ``mesh``; check where the bytes land, and compare
    ``ref_leaves`` of the stacked layers with the einsum reference computed
    on one device."""
    import jax
    import jax.numpy as jnp
    from repro.core import init_ligo_params, place_operator, plan_for
    from repro.core.ligo import resolve_expander
    from repro.kernels import ref
    from repro.models import init_params

    key_p, key_op = jax.random.split(jax.random.PRNGKey(seed))
    abstract = jax.eval_shape(lambda: init_params(cfg1, key_p))
    plan = plan_for(cfg1, cfg2, abstract)
    _, small_sh, _ = plan.shardings(mesh)
    sp = jax.jit(lambda: init_params(cfg1, key_p), out_shardings=small_sh)()
    op = place_operator(init_ligo_params(key_op, cfg1, cfg2), mesh)
    log(f"sharded: {cfg1.name} ({cfg1.param_count() / 1e9:.2f}B) -> "
        f"{cfg2.name} ({cfg2.param_count() / 1e9:.2f}B) {cfg2.dtype} on "
        f"mesh {dict(mesh.shape)}")
    t0 = time.perf_counter()
    big = plan.executor(mesh=mesh)(op, sp)
    jax.block_until_ready(big)
    wall = time.perf_counter() - t0

    per_dev = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(big):
        for shard in leaf.addressable_shards:
            per_dev[shard.device] += shard.data.nbytes
    total = sum(per_dev.values())
    for d, nb in per_dev.items():
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"sharded: device {d.id}: {nb / 2 ** 30:.3f} GiB of grown "
            f"params" + (f", peak {peak / 2 ** 30:.3f} GiB in use"
                         if peak else ""))
    log(f"sharded: grown tree {total / 2 ** 30:.3f} GiB in {wall:.2f} s "
        f"(first call, compile included)")
    check(min(per_dev.values()) >= total / (2 * len(per_dev)),
          f"grown params are not spread over the mesh: {per_dev}")

    kind = next(g.kind for g in plan.groups if g.kind)
    dev0 = mesh.devices.flat[0]
    errs = {}
    for name in ref_leaves:
        g = next(g for g in plan.groups if g.kind == kind and name in g.paths)
        width = jax.device_put(op["width"], dev0)
        B = resolve_expander(plan.exprs[g.in_ref], width, cfg1, cfg2, "in")
        A = resolve_expander(plan.exprs[g.out_ref], width, cfg1, cfg2, "out")
        w = jax.device_put(op["depth"][kind][name], dev0)
        W = jax.device_put(sp["layers"][kind][name], dev0)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.ligo_expand_full_ref)(
                w, B.astype(jnp.float32), A.astype(jnp.float32),
                W.astype(jnp.float32))
        errs[name] = rel_err(big["layers"][kind][name], want)
        del want
        log(f"sharded: {kind}/{name} {tuple(W.shape)} -> "
            f"{tuple(big['layers'][kind][name].shape)}: max rel err vs "
            f"einsum reference {errs[name]:.3e} (bound {BF16_TOL})")
        check(errs[name] <= BF16_TOL, f"{name} differs from the reference "
                                      f"by {errs[name]:.3e}")
    return {"per_device_bytes": {d.id: nb for d, nb in per_dev.items()},
            "wall_s": wall, "rel_err": errs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train -> LiGO hop -> serve on one chip; 4: only "
                         "the sharded 8B growth on a 2x2 mesh")
    args = ap.parse_args(argv)
    try:
        import repro.launch  # noqa: F401
    except ImportError as e:
        print(f"[smoke] FAIL: the repro package is not next to "
              f"chip_smoke.py ({e})", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[smoke] FAIL: no TPU: JAX found {devs[0].platform} devices "
              f"only; this smoke run has no CPU fallback", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    log(f"devices: {len(devs)} x {devs[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            from repro.configs import get_config, half_config
            from repro.launch.mesh import make_mesh
            target = get_config("llama3-8b")
            sharded_phase(half_config(target), target,
                          make_mesh((2, 2), ("data", "model")))
        else:
            from repro.trajectory import TrajectoryConfig
            train_phase(TrajectoryConfig.from_json(SCHEDULE), RUN_DIR)
            serve_phase(["--arch", "gpt2-base", "--live-grow-at", "8",
                         "--grow-to", "gpt2-medium", "--requests", "16"])
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; {clock}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
