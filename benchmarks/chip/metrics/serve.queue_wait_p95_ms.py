"""serve.queue_wait_p95_ms: 95th percentile of the ``queue_wait_ms`` the
program records on each ``serve.prefill`` span that starts in the traced
window: from a request's submission to its admission."""
from benchmarks.chip.lib import program


def read(run):
    win = program.window(run)
    if win is None:
        return None
    return program.p95([s.attrs["queue_wait_ms"]
                        for s in win.named("serve.prefill")
                        if "queue_wait_ms" in s.attrs])
