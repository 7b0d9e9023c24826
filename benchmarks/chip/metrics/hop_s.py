"""hop_s: the window over the number of whole LiGO hops completed in it
(host clock; every hop ends on ``block_until_ready`` of its outputs)."""


def read(run):
    return run.records.get("hop_s")
