"""ligo.traces: traces of the LiGO phase's chunk program in the traced
window, per whole hop: the ``traced`` count of every ``ligo.launch`` span,
the growth of ``core.traces["train_ligo"]`` across that launch."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    launches = [] if win is None else win.named("ligo.launch")
    if not launches:
        return None
    return program.per_hop(run, sum(s.attrs.get("traced", 0)
                                    for s in launches))
