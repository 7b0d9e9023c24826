"""train_tokens_per_s: every token of every train step in the window, over
the window, which ends when the last step's outputs are ready (host
clock)."""


def read(run):
    return run.records.get("train_tokens_per_s")
