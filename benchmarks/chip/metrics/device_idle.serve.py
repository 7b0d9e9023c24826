"""device_idle.serve: 1 - (union of the device's operation intervals) / the
traced stretch, over the seconds after the hop's swap (the whole window
when the hop did not complete), in percent."""
from benchmarks.chip.lib import trace

AFTER_S = 5.0


def read(run):
    if run.trace is None:
        return None
    t = run.records.get("t_swap")
    if t is None:
        return 100.0 * run.summary.idle_share
    return 100.0 * trace.idle_share_between(run.trace, t, t + AFTER_S)
