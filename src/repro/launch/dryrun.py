import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first initialisation). Do not move them.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds abstract (ShapeDtypeStruct) params/inputs, pjits the
appropriate step function (train_step / prefill / serve_step) with the
production sharding rules, compiles it for the 16×16 single-pod mesh and the
2×16×16 multi-pod mesh, and records:

- ``memory_analysis`` (bytes per device — proves the cell fits HBM),
- ``cost_analysis`` (FLOPs / bytes for the roofline),
- collective bytes parsed from the optimised HLO (all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute operand sizes),
- scan trip counts (layer stack, loss chunks) for trip-count-corrected FLOPs
  (XLA's HLO cost analysis counts while-loop bodies once; see
  repro/roofline/analysis.py).

Results are cached as JSON under artifacts/dryrun/<mesh>/<arch>/<shape>.json
so repeated invocations skip completed cells.

Usage:
    python -m repro.launch.dryrun --mesh single --all
    python -m repro.launch.dryrun --mesh multi --arch llama3-8b --shape train_4k
"""
import argparse
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import (ASSIGNED, SHAPES, TrainConfig, enumerate_cells,
                           get_config)
from repro.distributed.sharding import (batch_specs, named_shardings,
                                        params_pspecs, physical_spec,
                                        state_pspecs)
from repro.launch.mesh import make_production_mesh
from repro.models.inputs import input_specs
from repro.models.model import decode_step, init_params, prefill
from repro.optim import adamw_init
from repro.roofline.hlo import collect_hlo_stats
from repro.training.trainer import make_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def _loss_chunk_for(cfg, seq_len: int) -> int:
    # chunk the unembed+CE when logits would exceed ~256M elements
    if cfg.vocab_size * seq_len > 2 ** 27 and seq_len >= 1024:
        return 512
    return 0


def abstract_params(cfg):
    return jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))


def build_cell(cfg, shape, mesh, *, tuning: Optional[Dict[str, Any]] = None):
    """Returns (fn, example_args, in_shardings, out_shardings, meta)."""
    tuning = dict(tuning or {})
    if tuning.get("moe_data_shard"):
        cfg = cfg.scaled(moe_dispatch_shard="model_data")
    if tuning.get("capacity_factor"):
        cfg = cfg.scaled(capacity_factor=tuning["capacity_factor"])
    if tuning.get("moe_weight_gather"):
        cfg = cfg.scaled(moe_weight_gather=True)
    if tuning.get("moe_shardmap"):
        cfg = cfg.scaled(moe_impl="shard_map")
        tuning.setdefault("moe_layout", "shardmap")
    act_spec = (P("data", "model", None) if tuning.get("seq_shard") else None)
    p_sds = abstract_params(cfg)
    pspecs = params_pspecs(p_sds,
                           moe_layout=tuning.get("moe_layout", "fsdp"))
    p_sh = named_shardings(pspecs, mesh)
    specs = input_specs(cfg, shape)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    meta = {"arch": cfg.name, "shape": shape.name, "kind": shape.kind}

    if shape.kind == "train":
        o_sds = jax.eval_shape(adamw_init, p_sds)
        o_specs = params_pspecs_like(o_sds, pspecs)
        o_sh = named_shardings(o_specs, mesh)
        b_specs = batch_specs(specs["batch"], dp_size=dp)
        b_sh = named_shardings(b_specs, mesh)
        tcfg = TrainConfig(steps=10000, warmup_steps=100,
                           microbatches=tuning.get("microbatches", 1))
        lc = tuning.get("loss_chunk", _loss_chunk_for(cfg, shape.seq_len))
        fn = make_train_step(cfg, tcfg, loss_chunk=lc,
                             chunk_q=tuning.get("chunk_q", 2048),
                             chunk_k=tuning.get("chunk_k", 2048),
                             act_spec=act_spec,
                             bf16_cotangent=tuning.get("bf16_cotangent",
                                                       False),
                             p_bf16=tuning.get("p_bf16", False))
        args = (p_sds, o_sds, specs["batch"],
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (p_sh, o_sh, b_sh, NamedSharding(mesh, P()))
        out_sh = (p_sh, o_sh, None)
        meta["loss_chunk"] = lc
        return fn, args, in_sh, out_sh, meta

    if shape.kind == "prefill":
        b_specs = batch_specs(specs["batch"], dp_size=dp)
        b_sh = named_shardings(b_specs, mesh)

        def wrapped(params, batch):
            return prefill(params, cfg, batch, max_len=shape.seq_len,
                           chunk_q=tuning.get("chunk_q", 2048),
                           chunk_k=tuning.get("chunk_k", 2048),
                           act_spec=act_spec)

        args = (p_sds, specs["batch"])
        return wrapped, args, (p_sh, b_sh), None, meta

    # decode
    st_sds = specs["state"]
    st_specs = state_pspecs(st_sds, cfg,
                            model_size=mesh.shape.get("model", 1), dp_size=dp)
    st_sh = named_shardings(st_specs, mesh)
    b_specs = batch_specs(specs["batch"], dp_size=dp)
    b_sh = named_shardings(b_specs, mesh)

    def serve_step(params, state, batch):
        return decode_step(params, cfg, state, batch)

    args = (p_sds, st_sds, specs["batch"])
    return serve_step, args, (p_sh, st_sh, b_sh), (None, st_sh), meta


def params_pspecs_like(opt_sds, pspecs):
    """Optimizer-state specs mirror parameter specs (m, v; count replicated)."""
    import jax.tree_util as jtu

    def build(tree):
        if isinstance(tree, jax.ShapeDtypeStruct):
            return P()
        return tree

    # AdamWState(m=tree, v=tree, count=scalar)
    return type(opt_sds)(m=pspecs, v=pspecs, count=P())


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             tuning: Optional[Dict[str, Any]] = None,
             save: bool = True, tag: str = "") -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    fn, args, in_sh, out_sh, meta = build_cell(cfg, shape, mesh,
                                               tuning=tuning)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    hlo_stats = collect_hlo_stats(hlo_text)
    if save:
        try:
            import zstandard
            hdir = os.path.join(ARTIFACTS, "..", "hlo",
                                mesh_kind + (f"-{tag}" if tag else ""), arch)
            os.makedirs(hdir, exist_ok=True)
            with open(os.path.join(hdir, f"{shape_name}.hlo.zst"), "wb") as f:
                f.write(zstandard.ZstdCompressor(level=6).compress(
                    hlo_text.encode()))
        except Exception:
            pass
    result = {
        **meta,
        "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape),
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0)
                           + getattr(mem, "argument_size_in_bytes", 0)),
        },
        "cost": {"flops": cost.get("flops"),
                 "bytes": cost.get("bytes accessed"),
                 "transcendentals": cost.get("transcendentals")},
        "hlo": hlo_stats,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "tuning": tuning or {},
    }
    if save:
        out_dir = os.path.join(ARTIFACTS, mesh_kind + (f"-{tag}" if tag else ""),
                               arch)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{shape_name}.json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def reanalyze(mesh_kind: str, tag: str = "") -> int:
    """Re-parse saved compressed HLO into fresh stats (no recompilation)."""
    import zstandard
    hbase = os.path.join(ARTIFACTS, "..", "hlo",
                         mesh_kind + (f"-{tag}" if tag else ""))
    n = 0
    if not os.path.isdir(hbase):
        return 0
    for arch in sorted(os.listdir(hbase)):
        for fname in sorted(os.listdir(os.path.join(hbase, arch))):
            if not fname.endswith(".hlo.zst"):
                continue
            shape_name = fname[:-len(".hlo.zst")]
            jpath = os.path.join(ARTIFACTS,
                                 mesh_kind + (f"-{tag}" if tag else ""),
                                 arch, f"{shape_name}.json")
            if not os.path.exists(jpath):
                continue
            with open(os.path.join(hbase, arch, fname), "rb") as f:
                hlo = zstandard.ZstdDecompressor().decompress(
                    f.read()).decode()
            with open(jpath) as f:
                rec = json.load(f)
            rec["hlo"] = collect_hlo_stats(hlo)
            with open(jpath, "w") as f:
                json.dump(rec, f, indent=1)
            n += 1
            print(f"[reanalyze] {mesh_kind}/{arch}/{shape_name}", flush=True)
    return n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="re-parse saved HLO without recompiling")
    ap.add_argument("--preset", default=None, choices=[None, "optimized"],
                    help="optimized = §Perf winners: sequence-parallel "
                         "residual (train/prefill) + shard_map MoE")
    args = ap.parse_args()

    if args.reanalyze:
        n = reanalyze(args.mesh, args.tag)
        print(f"[reanalyze] {n} cells updated")
        return

    cells = enumerate_cells()
    if args.list:
        for c in cells:
            print(f"{c.key:45s} {'RUN' if c.runnable else 'SKIP(' + c.skip_reason + ')'}")
        return

    todo = [c for c in cells
            if (args.all or
                ((args.arch is None or c.arch == args.arch)
                 and (args.shape is None or c.shape.name == args.shape)))]
    ok = failed = skipped = cached = 0
    for c in todo:
        path = os.path.join(ARTIFACTS, args.mesh + (f"-{args.tag}" if args.tag else ""),
                            c.arch, f"{c.shape.name}.json")
        if not c.runnable:
            print(f"[dryrun] SKIP {c.key}: {c.skip_reason}", flush=True)
            skipped += 1
            continue
        if os.path.exists(path) and not args.force:
            cached += 1
            continue
        print(f"[dryrun] {args.mesh} {c.key} ...", flush=True)
        tuning = None
        if args.preset == "optimized":
            cfg_c = get_config(c.arch)
            tuning = {}
            # sequence-parallel residual: wins for attention-stack models;
            # measured counterproductive for ssm/hybrid (their chunkwise
            # scans re-gather T per block — see EXPERIMENTS.md §Perf)
            if (c.shape.kind in ("train", "prefill")
                    and cfg_c.family not in ("ssm", "hybrid")):
                tuning["seq_shard"] = True
            # explicit-collective MoE: wins for train/prefill; per-token
            # a2a overhead dominates single-token decode
            if cfg_c.n_experts and c.shape.kind in ("train", "prefill"):
                tuning["moe_shardmap"] = True
        try:
            r = run_cell(c.arch, c.shape.name, args.mesh, tag=args.tag,
                         tuning=tuning)
            print(f"[dryrun]   OK flops={r['cost']['flops']:.3e} "
                  f"peak={r['memory']['peak_bytes']/2**30:.2f}GiB "
                  f"compile={r['compile_s']:.1f}s", flush=True)
            ok += 1
        except Exception as e:  # noqa: BLE001 — record the failure, keep going
            failed += 1
            print(f"[dryrun]   FAIL {c.key}: {type(e).__name__}: "
                  f"{str(e)[:400]}", flush=True)
            traceback.print_exc()
    print(f"[dryrun] done ok={ok} cached={cached} failed={failed} "
          f"skipped={skipped}", flush=True)


if __name__ == "__main__":
    main()
