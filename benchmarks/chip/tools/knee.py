#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell to find its knee, on the chip.

    python3 benchmarks/chip/tools/knee.py --workload gpt2.serve_hop \\
        --rates 4,8,12,16 --seconds 30 --seed 5

Sets the cell up once, then serves one window per rate, each on a fresh
engine with its own live hop, with the cell's mix at that rate. For each
rate it prints the queue a third of the way in and when the last request
is due, the failures and the tails. The knee is the highest rate whose
queue at the end is no longer than at a third; the cell's traffic file
takes about four fifths of it. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import harness as H  # noqa: E402
from benchmarks.chip.lib import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    cell = H.find_cell(H.load_benchmark(ROOT), args.workload, BENCH)
    H.require_chips(cell.chips)
    H.use_cache(os.path.join(BENCH, ".jax_cache"))
    driver = cell.driver()
    ctx = H.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                    trace=False, t_start=time.perf_counter())
    state = driver.setup(ctx)
    state.pop("engine"), state.pop("hop")
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate=rate)
        reqs = traffic.schedule(mix, args.seconds, ctx.np_seed(99),
                                cell.config["src"]["vocab_size"])
        eng, hop = driver.new_engine(state, mix)
        hop.warm()
        seen = driver.serve(ctx, eng, hop, reqs, args.seconds, mix)
        out = driver.summarize(reqs, seen)
        print(json.dumps({"rate": rate, **{
            k: out.get(k) for k in ("attempted", "failed", "queue_third",
                                    "queue_end", "ttft_p95_ms", "itl_p95_ms",
                                    "stall_ms", "elapsed_s",
                                    "hop_completed")}}), flush=True)
        del eng, hop, seen
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
