"""mfu.train_step: operations one token needs from shapes (6 per matmul
weight plus attention, no recomputation: ``lib/flops``) times
``train_tokens_per_s``, over the chip's peak, in percent."""
from benchmarks.chip.lib import flops


def read(run):
    rate = run.records.get("train_tokens_per_s")
    if not rate:
        return None
    tr = run.cell.traffic
    m = run.cell.config[tr["model"]]
    return (100.0 * flops.train_flops_per_token(m, tr["seq"]) * rate
            / run.peaks["flops_bf16"])
