"""ligo.chunk_idle_s: device-idle seconds per whole LiGO hop inside the
phase's ``ligo.chunk`` spans (drawing and stacking batches, launching the
chunk program, syncing its losses), from the device trace split at the
program's span edges."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None or not win.named("grow"):
        return None
    return program.per_hop(run, win.idle_within("ligo.chunk"))
