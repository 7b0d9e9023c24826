"""Where the fused LiGO kernels sit in a trace, and their roofline share.

A kernel's events are found by name. Every apply launches each group on the
fused route once, so the kernel's events in the window come in rounds of
one launch per group.
"""
from __future__ import annotations

from benchmarks.chip.lib import flops

# Names the Pallas kernels' launches carry into the device trace. XLA names
# a Mosaic custom call after the name stack of its ``pallas_call``
# (``ligo_blend_expand_grouped.2`` in the apply,
# ``jvp_jit_ligo_blend_expand_grouped__.2`` and
# ``transpose_jvp_jit_ligo_blend_expand_bwd_fused___.1`` under the LiGO
# phase's gradient, as compiled for a v5e); the kernel body's own name is
# the other form a trace may give. The einsum route
# (``..._grouped_ref``) is not a kernel launch.
FWD = r"^_kernel|ligo_blend_expand_grouped(?!_ref)"
BWD = r"^_bwd_kernel|ligo_blend_expand_bwd_fused"


def roofline_share(run, pattern: str, count_fn):
    """Least seconds over measured seconds of the kernel ``pattern`` names,
    in percent; None when the trace holds no such event."""
    s, groups = run.summary, run.records.get("kernel_groups")
    if s is None or not groups:
        return None
    n = s.count_of(pattern)
    seconds = s.time_of(pattern)
    if n == 0 or seconds <= 0:
        return None
    per_round = 0.0
    for g in groups:
        args = {k: g[k] for k in ("G", "L1", "L2", "E", "I", "A", "Bd",
                                  "itemsize")}
        per_round += flops.roofline_seconds(*count_fn(**args),
                                            run.peaks["flops_bf16"],
                                            run.peaks["hbm_bw"])[0]
    return 100.0 * (n / len(groups)) * per_round / seconds
