"""setup.compile_s: seconds the backend spent compiling during set-up, from
JAX's monitoring events (about 0 when every program came from the
persistent cache)."""


def read(run):
    setup = run.records.get("setup")
    return None if setup is None else setup["compile_total_s"]
