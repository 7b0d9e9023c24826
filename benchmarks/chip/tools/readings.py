#!/usr/bin/env python3
"""The readings the limits are set from, on the chip, in one process.

    python3 benchmarks/chip/tools/readings.py --workload bert.ligo_phase \\
        --seeds 101,102,103 --control-seeds 101,102,103 \\
        --fault-seeds 201,202,203 --seconds 10

For every seed of ``--seeds`` it sets the cell up (which runs the program's
first steps, or for serving a short window at the cell's load), and prints
the numbers the check compares for the program and, on ``--control-seeds``,
for the control: the plain reference put in the program's place in the
precision below the configuration's. On ``--fault-seeds`` it does the same
with each fault of ``lib/faults.py`` planted in the program. One JSON line
per reading on standard output; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import faults as F  # noqa: E402
from benchmarks.chip.lib import harness as H  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def one(cell, driver, seed: int, seconds: float, control: bool,
        fault: str = None) -> dict:
    ctx = H.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                    t_start=time.perf_counter(), clock=None)
    t0 = time.perf_counter()
    name = cell.traffic["driver"]
    with (F.plant(name, fault) if fault else contextlib.nullcontext()):
        state = driver.setup(ctx)
        if name == "serve_hop":          # serving is checked on its window
            driver.window(ctx, state)
    t1 = time.perf_counter()
    out = driver.readings(ctx, state, control)
    return {"seed": seed, "fault": fault, "program_s": t1 - t0,
            "reference_s": time.perf_counter() - t1, **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = H.find_cell(H.load_benchmark(ROOT), args.workload, BENCH)
    H.require_chips(cell.chips)
    H.use_cache(os.path.join(BENCH, ".jax_cache"))
    driver = cell.driver()
    ctl = set(seeds(args.control_seeds))
    jobs = [(s, s in ctl, None)
            for s in sorted(set(seeds(args.seeds)) | ctl)]
    jobs += [(s, False, f) for f in F.FAULTS[cell.traffic["driver"]]
             for s in seeds(args.fault_seeds)]
    for s, control, fault in jobs:
        try:
            out = one(cell, driver, s, args.seconds, control, fault)
        except Exception as e:                   # noqa: BLE001
            # a reading that fails is reported, and the others still run
            traceback.print_exc()
            out = {"seed": s, "fault": fault, "error": repr(e)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
