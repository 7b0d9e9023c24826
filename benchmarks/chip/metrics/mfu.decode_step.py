"""mfu.decode_step: operations of every decode round in the traced window
(2 per matmul weight for each active sequence, plus attention over its
live length), over the device time of the decode program, over the chip's
peak, in percent."""
from benchmarks.chip.lib import flops

PROGRAM = r"decode_many"


def read(run):
    s, decodes = run.summary, run.records.get("decodes")
    if s is None or not decodes:
        return None
    seconds = s.time_of(PROGRAM, modules=True)
    if seconds <= 0:
        return None
    cfg = run.cell.config
    ops = sum(flops.decode_flops(cfg[side], [n]) for side, n in decodes)
    return 100.0 * ops / seconds / run.peaks["flops_bf16"]
