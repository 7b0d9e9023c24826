"""grow.idle_s: device-idle seconds per whole LiGO hop inside ``grow`` but
outside every ``ligo.chunk`` span (operator init, the phase's set-up, the
growth of parameters and moments), from the device trace split at the
program's span edges."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None or not win.named("grow"):
        return None
    return program.per_hop(run, win.idle_within("grow",
                                                outside=("ligo.chunk",)))
