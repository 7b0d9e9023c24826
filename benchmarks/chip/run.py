#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload bert.ligo_phase --seed 7 \\
        --seconds 30 --trace 0

Everything runs in this one process, which holds the chip. The run finds the
cell by name in ``BENCHMARK.json``, makes its weights and inputs from
``--seed``, sets up and warms every program the cell uses (``setup_s``),
measures for ``--seconds`` seconds, reads the device's peak memory, frees
the program's state and compares what the window's programs produced with
the plain reference (``correct``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window is profiled and the metrics are the cell's
per-layer metrics, read from the trace and the run's records, with the
device's busy time and a breakdown of where the time went.

Earlier lines on standard error give the device, the persistent cache's
hits and misses (the missed programs by name), the split of ``setup_s``,
the peak memory and the numbers compared with their limits. The last line
of standard output is one JSON object. Without a TPU, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import harness as H  # noqa: E402
from benchmarks.chip.lib import peaks as P  # noqa: E402
from benchmarks.chip.lib import trace as T  # noqa: E402


class Run:
    """What a metric reader sees: the run's records, the reduced trace
    (``--trace 1`` only), the cell and the chip's peaks."""

    def __init__(self, cell, records, trace, summary, peaks):
        self.cell = cell
        self.records = records
        self.trace = trace
        self.summary = summary
        self.peaks = peaks


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_metrics(specs, run) -> dict:
    out = {}
    for spec in specs:
        value = H.load_module("metrics", spec["name"],
                              run.cell.bench_dir).read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def execute(args, *, root: str = ROOT, platform: str = "tpu",
            t_start: float = None, peaks: dict = None) -> dict:
    """One run; returns the result object (the caller prints it).
    ``platform`` and ``peaks`` let a rehearsal on the CPU drive the rest of
    a run; a measured run takes the defaults."""
    t_start = H.process_start() if t_start is None else t_start
    bench_dir = os.path.join(root, "benchmarks", "chip")
    cell = H.find_cell(H.load_benchmark(root), args.workload, bench_dir)
    import jax
    import repro  # noqa: F401  (the system under test)
    devices = H.require_chips(cell.chips, platform)
    H.log(f"device: {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}; jax {jax.__version__}")
    if platform == "tpu":          # a rehearsal leaves the cache alone
        cache = H.use_cache(os.path.join(bench_dir, ".jax_cache"))
        H.log(f"compile cache: {cache}")
    ctx = H.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=t_start,
                    clock=H.CompileClock())
    ctx.mark("import")
    driver = cell.driver()
    state = driver.setup(ctx)
    t_window = time.perf_counter()
    ctx.records["setup_s"] = t_window - t_start
    setup = ctx.clock.snapshot()
    # import and init as marked; then the backend's compiling after init,
    # and the rest of the warm-up
    t_init, compiled_at_init = ctx.marks["init"]
    late = setup["compile_s"] - compiled_at_init
    split = {"import_s": ctx.marks["import"][0] - t_start,
             "init_s": t_init - ctx.marks["import"][0],
             "compile_s": late, "warm_s": t_window - t_init - late}
    ctx.records["setup"] = dict(split, compile_total_s=setup["compile_s"],
                                hits=setup["hits"], misses=setup["misses"])
    H.log(f"set-up {ctx.records['setup_s']:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in split.items()))
    H.log(f"persistent cache: {setup['hits']} hits, {setup['misses']} "
          f"misses; missed: {ctx.clock.missed or 'none'}")

    trace_dir = os.path.join(bench_dir, ".out", "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with H.trace_window(ctx, trace_dir):
        with ctx.span(T.WINDOW_SPAN):
            records = driver.window(ctx, state)
    ctx.records.update(records)
    after = ctx.clock.snapshot()
    in_window = after["compiles"] - setup["compiles"]
    H.log(f"window: {in_window} backend compiles, "
          f"{after['compile_s'] - setup['compile_s']:.3f} s compiling")
    peak = memory_peak(devices)
    H.log(f"memory: peak_bytes_in_use {peak}")

    summary = tr = None
    if ctx.trace:
        lines = T.CPU_LINES if platform == "cpu" else {}
        tr = T.load(T.find_xplane(trace_dir), **lines)
        summary = T.summarize(tr)
        shutil.rmtree(trace_dir, ignore_errors=True)
        with open(os.path.join(bench_dir, ".out", "last_trace.json"),
                  "w") as f:
            json.dump({"ops_s": summary.ops_s, "modules_s": summary.modules_s,
                       "op_counts": summary.op_counts,
                       "idle_by_span": summary.idle_by_span}, f)
        H.log(f"trace: busy {summary.busy_s:.4f} s of "
              f"{summary.window_s:.4f} s")

    checks = driver.check(ctx, state)
    del state
    run = Run(cell, ctx.records, tr, summary,
              peaks or P.peaks(devices[0].device_kind))
    specs = cell.per_layer if ctx.trace else cell.end_to_end
    metrics = read_metrics(specs, run)
    failed = int(ctx.records.get("failed", 0))
    correct = bool(checks) and all(c.ok for c in checks) and not failed
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": int(ctx.records.get("attempted", 0)),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    for c in checks:
        H.log(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    t_start = H.process_start()
    args = parse(argv)
    try:
        result = execute(args, t_start=t_start)
    except H.BenchError as e:
        H.log(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
