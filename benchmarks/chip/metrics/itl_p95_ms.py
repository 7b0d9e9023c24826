"""itl_p95_ms: 95th percentile over every gap between two consecutive
tokens of every request in the window, the gaps across the hop included
(host clock, stamped at the return of the step that made each token)."""


def read(run):
    return run.records.get("itl_p95_ms")
