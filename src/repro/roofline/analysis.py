"""Three-term roofline analysis over the dry-run artifacts.

Per (arch × shape × mesh) cell:

    compute    = HLO_dot_FLOPs_per_device / peak_FLOP/s        (197e12 bf16)
    memory     = HLO_bytes_per_device     / HBM_bw             (819e9 B/s)
    collective = wire_bytes_per_device    / ICI_link_bw        (50e9 B/s)

(all trip-count-corrected from the optimised HLO — see roofline/hlo.py; the
raw XLA cost_analysis numbers are reported alongside for reference).

The modelled step time is max(terms); the **roofline fraction** — the score
§Perf optimises — is

    fraction = (MODEL_FLOPS / (chips · peak)) / max(terms)

with MODEL_FLOPS = 6·N_active·tokens for training (2·N for inference), i.e.
the fraction of the modelled step spent on *useful* model FLOPs. The ratio
MODEL_FLOPS / HLO_FLOPS separately exposes remat/redundancy waste.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, peaks

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "artifacts", "dryrun")


def train_flops_per_step(cfg, global_batch: int, seq_len: int) -> float:
    """``6·N_active·tokens`` for ONE optimizer step — the same training-FLOP
    model :func:`model_flops` applies to the named ``train`` shapes, exposed
    for callers that know their batch geometry directly (the autogrow
    telemetry stream computes return-per-FLOP from it)."""
    return 6.0 * cfg.active_param_count() * global_batch * seq_len


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch              # one new token per sequence
    return 2.0 * n * tokens


def analyse_cell(rec: Dict) -> Dict:
    chips = rec["n_devices"]
    hlo = rec["hlo"]
    pk = peaks(PRODUCTION_DEVICE_KIND)
    compute = hlo["dot_flops"] / pk["flops_bf16"]
    memory = hlo["hbm_bytes"] / pk["hbm_bw"]
    collective = hlo["collective_wire_bytes"] / pk["ici_bw"]
    terms = {"compute": compute, "memory": memory, "collective": collective}
    bottleneck = max(terms, key=terms.get)
    step_time = max(terms.values())
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / chips / pk["flops_bf16"]
    fraction = useful / step_time if step_time > 0 else 0.0
    hlo_flops_global = hlo["dot_flops"] * chips
    advice = {
        "compute": ("cut non-model FLOPs (remat recompute, masked attention "
                    "blocks, MoE over-capacity) or raise per-chip utilisation"),
        "memory": ("shard saved activations (sequence-parallel residual), "
                   "chunk the unembed/CE, larger fused blocks"),
        "collective": ("reduce (all-)gather volume: better param layout, "
                       "overlap via latency-hiding scheduler, compress "
                       "cross-pod grads"),
    }[bottleneck]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "chips": chips,
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "bottleneck": bottleneck, "step_time_s": step_time,
        "model_flops": mf, "useful_s": useful,
        "roofline_fraction": fraction,
        "model_over_hlo": mf / hlo_flops_global if hlo_flops_global else 0.0,
        "peak_gib": rec["memory"]["peak_bytes"] / 2 ** 30,
        "fits_hbm": rec["memory"]["peak_bytes"] < 16 * 2 ** 30,
        "advice": advice,
        "raw_cost_flops": rec["cost"]["flops"],
    }


def load_cells(mesh: str = "single", tag: str = "") -> List[Dict]:
    base = os.path.join(ART, mesh + (f"-{tag}" if tag else ""))
    out = []
    if not os.path.isdir(base):
        return out
    for arch in sorted(os.listdir(base)):
        d = os.path.join(base, arch)
        for f in sorted(os.listdir(d)):
            if f.endswith(".json"):
                with open(os.path.join(d, f)) as fh:
                    out.append(json.load(fh))
    return out


def table(mesh: str = "single", tag: str = "") -> List[Dict]:
    return [analyse_cell(r) for r in load_cells(mesh, tag)]


def markdown_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute (s) | memory (s) | collective (s) | "
           "bottleneck | peak GiB | fits | 6ND/HLO | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        body += (f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
                 f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
                 f"{r['bottleneck']} | {r['peak_gib']:.1f} | "
                 f"{'Y' if r['fits_hbm'] else 'N'} | "
                 f"{r['model_over_hlo']:.2f} | "
                 f"{r['roofline_fraction']:.3f} |\n")
    return hdr + body
