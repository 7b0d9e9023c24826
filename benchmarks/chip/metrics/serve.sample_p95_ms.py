"""serve.sample_p95_ms: 95th percentile of the program's ``serve.sample``
spans that start in the traced window: from a decode round's outputs being
ready to every live slot's token picked and its request finished or not,
on the host."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None:
        return None
    return program.p95([s.dur_ms for s in win.named("serve.sample")])
