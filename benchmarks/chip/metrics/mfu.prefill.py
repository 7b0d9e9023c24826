"""mfu.prefill: operations of the true prompt tokens of every prefill in
the traced window (the admissions, and the hop's re-prefill of live
sessions), over the device time of the prefill program, over the chip's
peak, in percent. Padding to the prompt budget counts as waste, not
work."""
from benchmarks.chip.lib import flops

PROGRAM = r"prefill_one"


def read(run):
    s, pre = run.summary, run.records.get("prefills")
    if s is None or not pre:
        return None
    seconds = s.time_of(PROGRAM, modules=True)
    if seconds <= 0:
        return None
    cfg = run.cell.config
    ops = sum(flops.prefill_flops(cfg[side], n) for side, n in pre)
    return 100.0 * ops / seconds / run.peaks["flops_bf16"]
