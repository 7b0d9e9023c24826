"""ttft_p95_ms: 95th percentile, over every request due in the window, of
its first token's time minus the time it was due (host clock; a request
that never finishes counts as failed)."""


def read(run):
    return run.records.get("ttft_p95_ms")
