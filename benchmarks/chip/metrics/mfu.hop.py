"""mfu.hop: the operations one LiGO hop needs, counted from shapes in their
least-operation order (``lib/flops.ligo_hop_flops``), over ``hop_s`` and the
chip's peak, in percent."""
from benchmarks.chip.lib import flops


def read(run):
    hop_s = run.records.get("hop_s")
    if not hop_s:
        return None
    cfg, tr = run.cell.config, run.cell.traffic
    ops = flops.ligo_hop_flops(cfg["src"], cfg["dst"], tr["ligo_steps"],
                               tr["batch"] * tr["seq"], tr["seq"])
    return 100.0 * ops / hop_s / run.peaks["flops_bf16"]
