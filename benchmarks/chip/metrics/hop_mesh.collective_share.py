"""hop_mesh.collective_share: device time of the collectives (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute, their async
start and done halves included) over the devices' busy time in the traced
window of whole LiGO hops, both summed over the chips, in percent. An op is
a collective by its own name, not by the operands its text names; each
chip's collective time is the union of its collective events, so
overlapping ones count once."""
import re

from benchmarks.chip.lib import trace

COLLECTIVE = re.compile(r"^%?(all-gather|reduce-scatter|all-reduce|"
                        r"all-to-all|collective-permute)")


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window_of(run.trace)
    busy = coll = 0.0
    for evs in run.trace.devices.values():
        busy += trace.busy_ns(evs, lo, hi)
        coll += trace.busy_ns([e for e in evs if COLLECTIVE.match(e.name)],
                              lo, hi)
    return 100.0 * coll / busy if busy else None
