"""The four-chip LiGO cell (``starcoder2.hop4``) rehearsed on the CPU.

A tiny copy of the cell, with the real driver, traffic and readers and a
StarCoder2-shaped pair small enough for the CPU (grouped-query heads whose
group size changes and whose kv heads grow, sequences longer than the
window), runs whole through ``run.py`` on four virtual CPU devices laid out
as the cell's 2x2 (data, model) mesh, in a subprocess so that this process
keeps its one device: it comes out ``correct``, reports every end-to-end
and per-layer metric the cell lists, and comes out not correct with each
fault of the LiGO driver planted. No number read here is a device number.
"""
import json
import os
import subprocess
import sys

import pytest

import chipbench_tiny as tiny

CELL = "tiny.mesh"
REAL = "starcoder2.hop4"
SRC = dict(n_layers=2, d_model=96, n_heads=6, n_kv_heads=2, d_head=16,
           d_ff=192, vocab_size=512, max_seq=64, window=16)
DST = dict(n_layers=3, d_model=128, n_heads=8, n_kv_heads=4, d_head=16,
           d_ff=256, vocab_size=512, max_seq=64, window=16)
TRAFFIC = dict(batch=2, seq=32, ligo_steps=3, first_steps=2, loss_chunk=16)
# This size's own limits, set between the program's and the control's
# readings at this size on the CPU (seeds 5, 7, 8, 9): program loss
# 5.3e-5, operator 0.038, grown 0.0036 at most, against the control's
# 1.5e-3, 0.22, 0.064 at least.
LIMITS = {"loss_gap": 3e-4, "op_change_gap": 0.1, "grown_err": 0.015}


def make_root(tmp: str) -> str:
    """``chipbench_tiny``'s tree with the tiny four-device cell added."""
    root = tiny.make_root(tmp)
    bench_dir = os.path.join(root, "benchmarks", "chip")
    cfg = tiny._load(os.path.join(tiny.BENCH, "configs",
                                  "starcoder2-3b-to-7b.json"))
    cfg["src"].update(SRC)
    cfg["dst"].update(DST)
    tiny._dump(cfg, os.path.join(bench_dir, "configs", f"{CELL}.json"))
    tr = tiny._load(os.path.join(tiny.BENCH, "traffic",
                                 "ligo_hop_mesh.json"))
    tr.update(TRAFFIC)
    tiny._dump(tr, os.path.join(bench_dir, "traffic", f"{CELL}.json"))
    tiny._dump(LIMITS, os.path.join(bench_dir, "limits", f"{CELL}.json"))
    bench = tiny._load(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": CELL, "source": "tiny",
                             "file": f"benchmarks/chip/configs/{CELL}.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": CELL, "config": CELL, "traffic": CELL,
                               "chips": 4, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tiny._dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


def _regrowth_gaps(root: str) -> dict:
    """The largest difference, per tree, between the first hop's own grown
    parameters and moments and what the check grows again from its
    operator (``program_tree``)."""
    import time
    import jax
    import numpy as np
    from benchmarks.chip.lib import harness as H
    cell = H.find_cell(H.load_benchmark(root), CELL,
                       os.path.join(root, "benchmarks", "chip"))
    driver = cell.driver()
    kept, hop = {}, driver.hop

    def keeping(ctx, state, steps):
        big, info = hop(ctx, state, steps)
        kept.setdefault("big", jax.device_get(big))
        kept.setdefault("m", jax.device_get(info["opt_state"].m))
        kept.setdefault("v", jax.device_get(info["opt_state"].v))
        return big, info

    driver.hop = keeping
    ctx = H.Context(cell=cell, seed=7, seconds=1.0, trace=False,
                    t_start=time.perf_counter())
    state = driver.setup(ctx)
    out = {}
    for which in driver.TREES:
        again = jax.device_get(driver.program_tree(state, state["first"],
                                                   which))
        out[which] = max(float(np.max(np.abs(
            np.asarray(a, np.float32) - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(kept[which]),
                            jax.tree.leaves(again)))
    return out


def _runs(root: str):
    """In a process with four CPU devices: a measured run, a traced run,
    a measured run with each of the LiGO driver's faults planted, and the
    check's regrowth of the first hop's trees beside the hop's own."""
    from benchmarks.chip.lib import faults as F
    out = {"run": tiny.run(root, CELL), "traced": tiny.run(root, CELL,
                                                           trace=1),
           "regrowth": _regrowth_gaps(root)}
    for fault in F.FAULTS["ligo_hop"]:
        with F.plant("ligo_hop", fault):
            out[fault] = tiny.run(root, CELL, seed=11)
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = make_root(str(tmp_path_factory.mktemp("bench")))
    code = (f"import json, sys; sys.path[:0] = [{tiny.HERE!r}]\n"
            "import test_chipbench_mesh as M\n"
            f"print('RESULT ' + json.dumps(M._runs({root!r})))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=1200, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines()
            if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _listed(section: str):
    bench = tiny._load(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    return {m["name"] for m in bench[section]
            if REAL in m.get("workloads", [REAL])}


def test_run_is_correct_on_four_devices(runs):
    res = runs["run"]
    assert res["correct"] is True, res["checks"]
    assert res["device"] == dict(res["device"], platform="cpu", count=4)
    assert set(res["metrics"]) == _listed("end_to_end")
    assert set(res["checks"]) == set(LIMITS)


def test_traced_run_reports_every_listed_metric(runs):
    res = runs["traced"]
    assert res["correct"] is True
    assert set(res["metrics"]) == _listed("per_layer")
    assert res["metrics"]["ligo.groups_fused"]["value"] == 0  # CPU: einsum
    assert 0 <= res["metrics"]["hop_mesh.collective_share"]["value"] <= 100


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_makes_the_run_incorrect(runs, fault):
    res = runs[fault]
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_check_regrows_the_first_hops_trees_exactly(runs):
    """The check compares trees grown again from the first hop's operator
    by the hop's own calls: they are the hop's outputs, bit for bit."""
    assert runs["regrowth"] == {"big": 0.0, "m": 0.0, "v": 0.0}
