"""mfu.hop_mesh: the operations one LiGO hop needs, counted from shapes in
their least-operation order (``lib/flops.ligo_hop_flops``), over ``hop_s``
and the peak of all the cell's chips, in percent. Rotary positions have no
position table, so a model without learned positions counts none."""
from benchmarks.chip.lib import flops


def read(run):
    hop_s = run.records.get("hop_s")
    if not hop_s:
        return None
    cfg, tr = run.cell.config, run.cell.traffic
    src, dst = (m if m.get("rope", "learned") == "learned"
                else dict(m, max_seq=0) for m in (cfg["src"], cfg["dst"]))
    ops = flops.ligo_hop_flops(src, dst, tr["ligo_steps"],
                               tr["batch"] * tr["seq"], tr["seq"])
    return 100.0 * ops / hop_s / (run.cell.chips * run.peaks["flops_bf16"])
