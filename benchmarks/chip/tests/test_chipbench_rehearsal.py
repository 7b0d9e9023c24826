"""Each driver through a whole run on the CPU at a tiny size, and a cell
added as new files only (a configuration, a mix and a metric) that the
harness finds by name."""
import hashlib
import json
import os

import pytest

import chipbench_tiny as tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _check_line(res, names):
    assert KEYS <= set(res)
    assert list(res)[-1] == "checks"          # the compared numbers last
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.ligo", {"hop_s", "setup_s"}),
    ("tiny.serve", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
])
def test_end_to_end_run(root, cell, e2e):
    _check_line(tiny.run(root, cell), e2e)


def test_traced_run(root):
    res = tiny.run(root, "tiny.train", trace=1)
    _check_line(res, {"setup.compile_s", "mfu.train_step",
                      "device_idle.train"})
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def _digest(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(root):
    """A configuration, a mix and a per-layer metric added as files, with
    entries in BENCHMARK.json: no file the benchmark had is edited."""
    bench_dir = os.path.join(root, "benchmarks", "chip")
    before = _digest(bench_dir)
    cfg = tiny._load(os.path.join(root, "benchmarks/chip/configs/"
                                  "tiny.ligo.json"))
    cfg["dst"].update(n_layers=6)
    tiny._dump(cfg, os.path.join(bench_dir, "configs", "deeper.json"))
    mix = tiny._load(os.path.join(bench_dir, "traffic", "tiny.ligo.json"))
    mix.update(ligo_steps=2, first_steps=2)
    tiny._dump(mix, os.path.join(bench_dir, "traffic", "short_hop.json"))
    with open(os.path.join(bench_dir, "metrics", "hops_done.py"), "w") as f:
        f.write('"""hops_done: whole hops in the window."""\n\n\n'
                'def read(run):\n    return run.records.get("hops")\n')
    bench = tiny._load(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "deeper", "source": "tiny",
                             "file": "benchmarks/chip/configs/deeper.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "deeper.short_hop", "config": "deeper",
                               "traffic": "short_hop", "chips": 1,
                               "why": "rehearsal"})
    bench["per_layer"].append({"name": "hops_done", "unit": "hops",
                               "better": "higher", "source": "host_clock",
                               "layer": "LiGO phase and growth engine",
                               "moves": "hop_s",
                               "workloads": ["deeper.short_hop"]})
    bench["end_to_end"][0]["workloads"].append("deeper.short_hop")
    tiny._dump(bench, os.path.join(root, "BENCHMARK.json"))
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    res = tiny.run(root, "deeper.short_hop", trace=1)
    assert res["metrics"]["hops_done"]["value"] >= 1
