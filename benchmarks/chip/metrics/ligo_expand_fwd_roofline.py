"""ligo_expand_fwd_roofline: the least time the fused blend-expand forward
kernel's launches need (each launch at the larger of its operations over
the peak and its bytes over HBM bandwidth, counted from its group's shapes
as the plan lists them) over the summed device time of the kernel's events
in the trace, in percent."""
from benchmarks.chip.lib import flops, kernels


def read(run):
    return kernels.roofline_share(run, kernels.FWD, flops.blend_expand_fwd)
