"""Tiny copies of the benchmark's cells, for rehearsals on the CPU.

``make_root(tmp)`` copies the benchmark's files into ``tmp`` with a
``BENCHMARK.json`` whose cells run the real drivers and metric readers on
configurations small enough for the CPU (same structure and dtype, tiny
widths), and ``run(root, cell, ...)`` drives one whole run there without
the look for a chip.
"""
import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# Peak rates for readers on the CPU; no number read with them is a device
# number.
CPU_PEAKS = {"flops_bf16": 1e12, "hbm_bw": 1e11, "hbm_bytes": 1e10}
SRC = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16,
           d_ff=128, vocab_size=512, max_seq=64)
DST = dict(n_layers=4, d_model=96, n_heads=6, n_kv_heads=6, d_head=16,
           d_ff=192, vocab_size=512, max_seq=64)
# (tiny cell, real cell, config file, traffic file, traffic overrides,
# size overrides, limits). The limits are this size's own, set between
# the readings of the program and of the control at this size on the CPU
# (seeds 5, 7, 8, 9): ligo program loss 1.4e-4, operator 0.029, grown
# 0.0035 against control 2.1e-3, 0.42, 0.063; train program 4.5e-5, 0.0035,
# 0.0020 against 7.6e-4, 0.0091, 0.0053; serve (about 430 served tokens)
# program at most 0.0039 against control at least 0.016 (a vocabulary of
# 8192 gives the greedy tokens near-ties to lose).
CELLS = [
    ("tiny.ligo", "bert.ligo_phase", "bert-small-to-base", "ligo_phase",
     dict(batch=2, seq=32, ligo_steps=4, first_steps=4), {},
     {"loss_gap": 6e-4, "op_change_gap": 0.12, "grown_err": 0.015}),
    ("tiny.train", "bert.train", "bert-small-to-base", "train",
     dict(batch=2, seq=32, pool=4), {},
     {"loss_gap": 2.5e-4, "grad_gap": 0.006, "update_gap": 0.004}),
    ("tiny.serve", "gpt2.serve_hop", "gpt2-base-to-medium", "serve_hop",
     dict(rate=40.0, slots=8, prompt_budget=24, gen_budget=32,
          prompt={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
          output={"median": 16, "sigma": 0.5, "min": 4, "max": 32},
          check_requests=24, drain_s=30), {"vocab_size": 8192},
     {"served_logit_gap": 0.008}),
]


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    """A checkout-like tree under ``tmp`` holding the tiny cells."""
    dst = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        ".jax_cache", ".out", "tests", "__pycache__"))
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for tiny, real, cfg_name, mix, over, sizes, limits in CELLS:
        cfg = _load(os.path.join(BENCH, "configs", f"{cfg_name}.json"))
        cfg["src"].update(SRC, **sizes)
        cfg["dst"].update(DST, **sizes)
        cfg_file = f"benchmarks/chip/configs/{tiny}.json"
        _dump(cfg, os.path.join(tmp, cfg_file))
        tr = _load(os.path.join(BENCH, "traffic", f"{mix}.json"))
        tr.update(over)
        _dump(tr, os.path.join(dst, "traffic", f"{tiny}.json"))
        bench["configs"].append({"name": tiny, "source": "tiny",
                                 "file": cfg_file, "reduced": [],
                                 "why": "rehearsal"})
        bench["workloads"].append({"name": tiny, "config": tiny,
                                   "traffic": tiny, "chips": 1,
                                   "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
        os.makedirs(os.path.join(dst, "limits"), exist_ok=True)
        _dump(limits, os.path.join(dst, "limits", f"{tiny}.json"))
    _dump(bench, os.path.join(tmp, "BENCHMARK.json"))
    return tmp


def run(root: str, cell: str, *, seed: int = 12345678901,
        seconds: float = 1.0, trace: int = 0) -> dict:
    from benchmarks.chip import run as RUN
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    return RUN.execute(args, root=root, platform="cpu", peaks=CPU_PEAKS)
