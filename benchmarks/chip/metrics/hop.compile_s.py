"""hop.compile_s: seconds the backend spent compiling in the traced window,
per whole LiGO hop, from the program's own compile events (each names the
innermost program span open on the compiling thread)."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None:
        return None
    return program.per_hop(run, sum(c.get("secs", 0.0)
                                    for c in win.compiles))
