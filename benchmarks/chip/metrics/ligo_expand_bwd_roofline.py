"""ligo_expand_bwd_roofline: as ``ligo_expand_fwd_roofline``, for the fused
backward kernel that emits dW, dB and dw in one pass."""
from benchmarks.chip.lib import flops, kernels


def read(run):
    return kernels.roofline_share(run, kernels.BWD, flops.blend_expand_bwd)
