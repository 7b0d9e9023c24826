#!/usr/bin/env python3
"""``tools/readings.py`` for a cell whose driver plants another driver's
faults.

    python3 benchmarks/chip/tools/readings_as.py --workload starcoder2.hop4 \\
        --seeds 101 --control-seeds 101 --fault-seeds 201 --seconds 10

A driver that reaches the program through the same functions as another
one names that driver in ``FAULTS_AS`` (``ligo_hop_mesh`` names
``ligo_hop``, whose faults patch ``repro.core.grow``). This runs
``tools/readings.py`` with the faults of ``lib/faults.py`` for that driver
planted under the cell's own: the same arguments, the same output.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip.lib import faults as F  # noqa: E402
from benchmarks.chip.lib import harness as H  # noqa: E402
from benchmarks.chip.tools import readings  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    name = ap.parse_known_args(argv)[0].workload
    cell = H.find_cell(H.load_benchmark(ROOT), name, BENCH)
    own = cell.traffic["driver"]
    alias = getattr(cell.driver(), "FAULTS_AS", None)
    if alias:
        plant = F.plant
        F.FAULTS[own] = F.FAULTS[alias]
        F.plant = lambda d, fault: plant(alias if d == own else d, fault)
    return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
