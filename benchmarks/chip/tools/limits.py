#!/usr/bin/env python3
"""Set a cell's limits from the readings ``tools/readings.py`` printed.

    python3 benchmarks/chip/tools/limits.py --workload bert.ligo_phase \\
        readings.txt [more.txt ...] [--write]

For every number the check compares:

- the lower reading is the largest the program gave over its seeds;
- the upper reading is the smallest the control gave, where that is three
  times the lower or more; in a training cell (a driver with
  ``TRAINING = True``) also the smallest of each planted fault that reads
  ten times the lower or more (three times for a state left unchanged);
- the limit lies between them, six tenths of the way from the lower to the
  upper on a log scale, so that more of the room is above the lower.

A number with no upper reading gets no limit and is reported. With
``--write`` the limits go to ``limits/<cell>.json``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections import defaultdict

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import harness as H  # noqa: E402

SHARE = 0.6
FAULT_FACTOR = {"unchanged": 3.0}          # other faults: 10


def collect(paths):
    prog, ctl = defaultdict(list), defaultdict(list)
    faults = defaultdict(lambda: defaultdict(list))
    errors = []
    for path in paths:
        with open(path) as f:
            for line in f:
                if not line.startswith("{"):
                    continue
                r = json.loads(line)
                if "error" in r:
                    errors.append(r)
                elif r.get("fault"):
                    for k, v in r["program"].items():
                        faults[k][r["fault"]].append(v)
                else:
                    for k, v in r["program"].items():
                        prog[k].append(v)
                    for k, v in r.get("control", {}).items():
                        ctl[k].append(v)
    return prog, ctl, faults, errors


def limits(prog, ctl, faults, training: bool):
    out, notes = {}, {}
    for k, vals in prog.items():
        lower = max(vals)
        cands = []
        if ctl.get(k) and min(ctl[k]) >= 3 * lower:
            cands.append(("control", min(ctl[k])))
        if training:
            for f, vs in faults.get(k, {}).items():
                if min(vs) >= FAULT_FACTOR.get(f, 10.0) * lower:
                    cands.append((f, min(vs)))
        note = {"lower": lower, "seeds": len(vals),
                "control_min": min(ctl[k]) if ctl.get(k) else None,
                "faults_min": {f: min(vs) for f, vs
                               in faults.get(k, {}).items()}}
        if cands:
            by, upper = min(cands, key=lambda c: c[1])
            out[k] = float(math.exp(math.log(lower) + SHARE
                                    * (math.log(upper) - math.log(lower))))
            note.update(upper=upper, upper_from=by, limit=out[k])
        notes[k] = note
    return out, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--write", action="store_true")
    ap.add_argument("paths", nargs="+")
    args = ap.parse_args(argv)
    cell = H.find_cell(H.load_benchmark(ROOT), args.workload, BENCH)
    training = bool(getattr(cell.driver(), "TRAINING", False))
    prog, ctl, faults, errors = collect(args.paths)
    out, notes = limits(prog, ctl, faults, training)
    for e in errors:
        print(f"reading failed: {json.dumps(e)}")
    for k, n in notes.items():
        print(f"{k}: {json.dumps(n)}")
    if args.write:
        os.makedirs(os.path.join(BENCH, "limits"), exist_ok=True)
        with open(os.path.join(BENCH, "limits", f"{cell.name}.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
