"""hop.stall_ms: the longest wait in the window for a return of
``engine.step()`` while the engine had work, from the previous return or
from when work reached an idle engine (host clock). Idle stretches between
arrivals do not count; the hop's begin, polls and swap between two steps
do."""


def read(run):
    return run.records.get("stall_ms")
