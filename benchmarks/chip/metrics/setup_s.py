"""setup_s: process start to the start of the measured window (host
clock): import, weights and inputs, compilation and warm-up."""


def read(run):
    return run.records.get("setup_s")
