"""hop.engine_block_ms: how long the live hop held the engine's thread in
the traced window: the summed ``hop.cache-grow`` (migrating the live
sessions' caches) and ``hop.swap`` spans of the program."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None:
        return None
    spans = win.named("hop.cache-grow") + win.named("hop.swap")
    return sum(s.dur_ms for s in spans) if spans else None
