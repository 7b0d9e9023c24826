"""serve.prefill_p95_ms: 95th percentile of the program's ``serve.prefill``
spans that start in the traced window: from a request's admission to its
first token picked on the host."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None:
        return None
    return program.p95([s.dur_ms for s in win.named("serve.prefill")])
